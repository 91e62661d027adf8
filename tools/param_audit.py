"""Generate the parameter-coverage table for COVERAGE.md.

Compares the reference's canonical parameter list (extracted from
src/io/config_auto.cpp parameter2aliases — the same generated table the
reference's ~600 documented names collapse into) against this
framework's Config table, and classifies every reference parameter as:

  implemented   — present in the table AND read by engine code
  accepted-noop — present in the table, intentionally inert here, with
                  the reason (device/threading semantics the TPU stack
                  replaces by construction)
  missing       — not recognized at all (would warn "Unknown parameter")

Run:  python tools/param_audit.py /path/to/reference > table.md
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# why each accepted parameter is intentionally inert on this stack
NOOP_REASONS = {
    "num_threads": "XLA owns intra-device parallelism (SURVEY 2.6; no host thread pool)",
    "device_type": "single TPU backend; the Pallas learner IS the device learner",
    "deterministic": "TPU/XLA execution is deterministic by construction",
    "force_col_wise": "one tuned row-wise histogram strategy (TrainingShareStates by-design row)",
    "force_row_wise": "row-wise is the only (and always) layout",
    "histogram_pool_size": "per-leaf HBM hist slots; no LRU pool needed at TPU HBM sizes",
    "is_enable_sparse": "dense u8/u16 device matrix; EFB handles sparsity (SURVEY 2.3)",
    "pre_partition": "distributed loading shards by rank in parallel/distributed.py",
    "two_round": "native parser streams; no two-round memory mode needed",
    "precise_float_parser": "the C++ text parser always parses exactly (strtod)",
    "parser_config_file": "no pluggable parser plugins; CSV/TSV/LibSVM built in",
    "machine_list_filename": "cluster bootstrap belongs to jax.distributed, not a machine file",
    "gpu_platform_id": "no OpenCL platform concept on TPU",
    "gpu_device_id": "device selection via JAX platform config",
    "gpu_use_dp": "histograms are f32",
    "num_gpu": "multi-chip via jax.sharding Mesh, not a device count knob",
}


def reference_params(ref_root):
    src = open(os.path.join(ref_root, "src/io/config_auto.cpp")).read()
    m = re.search(r"Config::parameter2aliases\(\)\s*{(.*?)\n}", src, re.S)
    return sorted(set(re.findall(r'\{"([a-z0-9_]+)",', m.group(1))))


def engine_usage():
    """Parameter names referenced anywhere outside the config table."""
    text = ""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for base, _, files in os.walk(os.path.join(root, "lightgbm_tpu")):
        for f in files:
            if f.endswith((".py", ".cpp")) and f != "config.py":
                text += open(os.path.join(base, f)).read()
    for f in ("bench.py", "tpu_selfcheck.py"):
        p = os.path.join(root, f)
        if os.path.exists(p):
            text += open(p).read()
    return text


def main():
    ref_root = sys.argv[1] if len(sys.argv) > 1 else "/root/reference"
    from lightgbm_tpu.config import _PARAM_BY_NAME, _ALIAS2NAME
    refp = reference_params(ref_root)
    text = engine_usage()
    rows = []
    counts = {"implemented": 0, "accepted-noop": 0, "missing": 0}
    for name in refp:
        canon = _ALIAS2NAME.get(name)
        if canon is None:
            status, note = "missing", "warns Unknown parameter"
        elif name in NOOP_REASONS:
            status, note = "accepted-noop", NOOP_REASONS[name]
        else:
            used = (re.search(r"\.%s\b" % re.escape(canon), text)
                    or re.search(r"['\"]%s['\"]" % re.escape(canon), text))
            if used:
                status, note = "implemented", ""
            else:
                status, note = "accepted-noop", "accepted; no engine read"
        counts[status] += 1
        rows.append((name, status, note))
    print("| reference param | status | note |")
    print("|---|---|---|")
    for name, status, note in rows:
        print(f"| `{name}` | {status} | {note} |")
    print()
    print(f"**{counts['implemented']} implemented, "
          f"{counts['accepted-noop']} accepted-noop, "
          f"{counts['missing']} missing** of {len(refp)} reference "
          "canonical parameters; unknown keys warn "
          "(`Unknown parameter: <k>`), matching config.h:1242.")


if __name__ == "__main__":
    main()
