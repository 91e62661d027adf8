"""Start-up contracts the chip depends on, checked on the CPU: importing
the package initialises no backend (a chip belongs to one process), the
compile cache goes where JAX_COMPILATION_CACHE_DIR says or to the fixed
<checkout>/.jax_cache, and chip_smoke.py refuses to report without a TPU
while its phases pass a toy-size rehearsal."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", **env)
    args = code_or_args if isinstance(code_or_args, list) \
        else ["-c", code_or_args]
    return subprocess.run([sys.executable] + args, env=full, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)


def test_import_initialises_no_backend():
    r = _run("import lightgbm_tpu, lightgbm_tpu.cli, lightgbm_tpu.serving\n"
             "from jax._src import xla_bridge\n"
             "assert not xla_bridge.backends_are_initialized()\n")
    assert r.returncode == 0, r.stderr[-2000:]


def test_compile_cache_follows_env_or_fixed_checkout_path():
    show = ("import jax, lightgbm_tpu\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = _run(show, JAX_COMPILATION_CACHE_DIR="/some/dir")
    assert r.stdout.strip() == "/some/dir", (r.stdout, r.stderr[-2000:])
    r = _run(show)
    assert r.stdout.strip() == os.path.join(ROOT, ".jax_cache"), \
        (r.stdout, r.stderr[-2000:])


def test_chip_smoke_without_tpu_fails_and_prints_no_result():
    r = _run([os.path.join(ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_rehearsal_passes_on_cpu(capsys):
    sys.path.insert(0, ROOT)
    import chip_smoke
    chip_smoke.main(["--rehearse"])
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert "rehearsal passed on platform=cpu" in out.splitlines()[-1]
    assert '"ok"' not in out
