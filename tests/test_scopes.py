"""The ``lgbm.<phase>`` scopes of the training step and the scope table the
benchmark reads (lightgbm_tpu/obs/scopes.py, benchmark/readers/
device_phase.py).

* ``phase_of`` on a fixed HLO snippet;
* scopes add no operation: the toy step lowers to the same text with
  ``jax.named_scope`` turned off;
* registering a program frees the booster and traces or compiles nothing a
  run without registration does not;
* every kernel plan the CPU reaches yields a table that names the histogram,
  the search and the partition;
* the benchmark's reader on a synthetic ``op_seconds``;
* the Pallas partition and histogram kernels compile for ``v5e:2x2`` under
  their own names, and the toy step's table gives them their phases;
* the one-chip layout programs compile for ``v5e:2x2`` with no row folded
  into a constant.
"""

import contextlib
import gc
import importlib.util
import os
import re
import types
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what XLA:TPU prints (cut from the step's text compiled for v5e): a while
# body with a Pallas custom call, a fusion that kept its op_name, one that
# lost it, an instruction with none, and the fusion bodies they call
SNIPPET = '''HloModule jit_lgbm_fused_step, is_scheduled=true

%fused_computation.1 (param_0.2: f32[8,128]) -> f32[8,128] {
  %param_0.2 = f32[8,128]{1,0} parameter(0)
  %constant.9 = f32[] constant(1), metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/vmap(lgbm.search)/lgbm.search/broadcast_in_dim"}
  %broadcast.4 = f32[8,128]{1,0} broadcast(%constant.9), dimensions={}
  ROOT %add.7 = f32[8,128]{1,0} add(%param_0.2, %broadcast.4), metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/lgbm.histogram/add"}
}

%fused_computation.2 (param_0.3: f32[8,128]) -> f32[1,128] {
  %param_0.3 = f32[8,128]{1,0} parameter(0)
  %constant.10 = s32[] constant(0), metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/lgbm.search/iota"}
  ROOT %slice.5 = f32[1,128]{1,0} slice(%param_0.3), slice={[2:3], [0:128]}, metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/slice"}
}

%fused_computation.3 (param_0.4: f32[8,128]) -> f32[8,128] {
  %param_0.4 = f32[8,128]{1,0} parameter(0)
  %neg.1 = f32[8,128]{1,0} negate(%param_0.4), metadata={op_name="jit(lgbm_fused_step)/lgbm.gradients/neg"}
  ROOT %exp.1 = f32[8,128]{1,0} exponential(%neg.1), metadata={op_name="jit(lgbm_fused_step)/lgbm.score_update/exp"}
}

%region_0.3 (lhs: f32[], rhs: f32[]) -> f32[] {
  %lhs = f32[] parameter(0)
  %rhs = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%lhs, %rhs), metadata={op_name="jit(lgbm_fused_step)/lgbm.search/reduce_sum"}
}

%wide.body.7 (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %arg = (s32[], f32[8,128]{1,0}) parameter(0)
  %get-tuple-element.2 = f32[8,128]{1,0} get-tuple-element(%arg), index=1
  %lgbm_partition.7 = f32[8,128]{1,0} custom-call(%get-tuple-element.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/closed_call/lgbm.partition/lgbm.partition/lgbm_partition/pallas_call" stack_frame_id=6}
  %fusion.8 = f32[8,128]{1,0} fusion(%lgbm_partition.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/lgbm.histogram/add" stack_frame_id=7}
  %broadcast_select_fusion.15 = f32[1,128]{1,0} fusion(%fusion.8), kind=kLoop, calls=%fused_computation.2
  %two_phase_fusion = f32[8,128]{1,0} fusion(%fusion.8), kind=kLoop, calls=%fused_computation.3
  %reduce.3 = f32[] reduce(%fusion.8, %constant.2), dimensions={0,1}, to_apply=%region_0.3, metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while/body/lgbm.search/reduce_sum"}
  %copy.12 = f32[8,128]{0,1} copy(%fusion.8)
  ROOT %tuple.2 = (s32[], f32[8,128]{1,0}) tuple(%get-tuple-element.2, %fusion.8)
}

ENTRY %main.11 (x.1: f32[8,128]) -> f32[8,128] {
  %x.1 = f32[8,128]{1,0} parameter(0), metadata={op_name="x"}
  %while.2 = (s32[], f32[8,128]{1,0}) while(%tuple.1), condition=%cond.4, body=%wide.body.7, metadata={op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while"}
  ROOT %dynamic-update-slice.3 = f32[8,128]{1,0} dynamic-update-slice(%x.1, %y, %c, %c), metadata={op_name="jit(lgbm_fused_step)/lgbm.score_update/scatter-add"}
}
'''


def test_phase_of_on_a_fixed_snippet():
    table = scopes.phase_of(SNIPPET)
    # the kernel's own name reaches the instruction, nested scopes read
    # innermost
    assert table["lgbm_partition.7"] == "partition"
    assert table["fusion.8"] == "histogram"
    assert table["reduce.3"] == "search"
    assert table["while.2"] == "bookkeeping"
    assert table["dynamic-update-slice.3"] == "score_update"
    # a fusion the compiler left without an op_name takes the one phase of
    # its body (the shared constant's `lgbm.search` does not vote) ...
    assert table["broadcast_select_fusion.15"] == "bookkeeping"
    # ... and none where the body carries two
    assert table["two_phase_fusion"] is None
    # no scope, no phase: still listed, so that a name another program
    # gives a phase to reads as ambiguous
    assert table["copy.12"] is None and table["x.1"] is None
    # fusion bodies and scalar regions are no operations of their own
    for inner in ("add.7", "slice.5", "add.1", "constant.9", "lhs"):
        assert inner not in table


def test_unknown_phase_or_program_is_refused():
    with pytest.raises(ValueError):
        scopes.scope("partitoin")
    with pytest.raises(ValueError):
        scopes.phase("histograms")
    with pytest.raises(ValueError):
        scopes.register("train.step", object())


# ---------------------------------------------------------------------------
# the toy step
# ---------------------------------------------------------------------------
def _toy(n=1500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (2 * X[:, 0] + X[:, 1] - X[:, 2] > 0).astype(float)
    return X, y


def _booster(extra=None, seed=0):
    X, y = _toy(seed=seed)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    params.update(extra or {})
    return lgb.Booster(params, lgb.Dataset(X, label=y))


def _step_text(bst):
    """StableHLO of the booster's fused step without locations: named
    scopes live in locations only."""
    g = bst._gbdt
    pb, ghi = g._init_phys(g.learner._part0, g.scores, *g._phys_payloads)
    feat_used = jnp.zeros((g.learner.F,), bool)
    return g._fused_phys.lower(pb, ghi, g._feature_mask(0), 1,
                               feat_used).as_text()


@pytest.mark.parametrize("extra", [{"tpu_frontier_k": 1},
                                   {"tpu_frontier_k": 4}],
                         ids=["k1", "frontier_k4"])
def test_scopes_add_no_operation(monkeypatch, extra):
    with_scopes = _step_text(_booster(extra))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _step_text(_booster(extra))
    assert "stablehlo" in with_scopes
    assert with_scopes == without


PLANS = {"frontier_k4": {"tpu_frontier_k": 4},
         "k1": {"tpu_frontier_k": 1},
         "mega_xla": {"tpu_megakernel": "xla", "tpu_frontier_k": 1}}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_every_cpu_plan_names_its_phases(plan):
    scopes._reset()
    bst = _booster(PLANS[plan])
    kp = bst._gbdt.kernel_plan()
    assert kp["frontier_k"] == PLANS[plan]["tpu_frontier_k"]
    assert kp["mega"] == ("xla" if plan == "mega_xla" else "off")
    bst.update()
    scores = np.asarray(bst._gbdt.scores)
    assert scores.shape == (1500,)
    table = scopes.scope_table()
    assert set(table) == set(scopes.PROGRAMS)
    step = set(table["train.fused_step"].values())
    assert {"histogram", "search", "gradients", "score_update",
            "bookkeeping"} <= step
    assert step & {"partition", "split_mega"}
    if plan == "mega_xla":
        assert "split_mega" in step
    else:
        assert "hist_state" in step
    assert set(table["train.scores_read"].values()) == {"scores_read", None}
    assert "layout_init" in table["train.layout_init"].values()


def _run(register, monkeypatch):
    """(backend compiles or fetches, traces of the fused step, bytes live
    after the booster is gone, the booster died) of two iterations and a
    scores read, with or without the registry."""
    scopes._reset()
    obs.get().reset(mode="counters")
    if not register:
        monkeypatch.setattr(scopes, "register_call",
                            lambda program, jitted, *args: None)
    compiles = []

    def listen(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        bst = _booster(seed=7)
        for _ in range(2):
            bst.update()
        jax.block_until_ready(bst._gbdt.scores)
        bst.update()
        jax.block_until_ready(bst._gbdt._phys)
        traces = obs.get().report()["compiles"]["train.fused_step"]
        died = weakref.ref(bst._gbdt)
        learner = weakref.ref(bst._gbdt.learner)
        del bst
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        return (len(compiles), traces, live,
                died() is None and learner() is None)
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(listen)
        obs.get().reset(mode="off")
        monkeypatch.undo()


def test_registry_frees_the_booster_and_compiles_nothing(monkeypatch):
    base = sum(a.nbytes for a in jax.live_arrays())
    without = _run(False, monkeypatch)
    with_registry = _run(True, monkeypatch)
    assert set(scopes.scope_table()) == set(scopes.PROGRAMS)
    # the same count of backend compilations (or cache fetches), one trace of
    # the step per booster, and nothing of the booster left alive
    assert with_registry[0] == without[0]
    assert with_registry[1] == without[1] == 1
    assert with_registry[3] and without[3]
    assert with_registry[2] == without[2] <= base + 4096


# ---------------------------------------------------------------------------
# the frontier loop's shape: what it carries, and what a step may move
# ---------------------------------------------------------------------------
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_REFERS = re.compile(r"(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _elements(dims):
    return int(np.prod([int(d) for d in dims.split(",") if d] or [1]))


def _computations(text):
    """{computation name: its instruction lines} of an HLO module's text."""
    out, current = {}, None
    for line in text.splitlines():
        head = scopes._COMPUTATION.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
        elif current is not None and scopes._INSTRUCTION.match(line):
            current.append(line)
    return out


def _reachable(computations, roots):
    """The computations a loop runs: its body and condition and whatever
    they call."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for line in computations[name]:
            todo += _REFERS.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _window_write(line, computations):
    """Is the instruction an in-place window write: a dynamic-update-slice,
    or a fusion whose body ends in one?"""
    kind = line.split(" = ", 1)[1]
    if " dynamic-update-slice(" in kind:
        return True
    return any(" dynamic-update-slice(" in ln
               for c in scopes._CALLS.findall(line)
               for ln in computations.get(c, ()) if "ROOT " in ln)


def _frontier_loop(text):
    """(the line of the frontier body's while instruction, {computation:
    lines}, the operations that loop runs) of a compiled fused step.  An
    operation is (line, phase, element type, dims) of an instruction that
    the scope table lists and that yields one array of its own: no tuple,
    no get-tuple-element, no nested loop."""
    computations = _computations(text)
    loops = [ln for lines in computations.values() for ln in lines
             if " while(" in ln
             and 'op_name="jit(lgbm_fused_step)/lgbm.bookkeeping/while"'
             in ln]
    assert len(loops) == 1
    table = scopes.phase_of(text)
    ops = []
    for name in _reachable(computations,
                           _REFERS.findall(loops[0].split(" while(")[1])):
        for line in computations[name]:
            inst = scopes._INSTRUCTION.match(line).group(1)
            kind = line.split(" = ", 1)[1]
            if inst in table and not kind.lstrip().startswith("(") \
                    and " get-tuple-element(" not in kind \
                    and " while(" not in kind:
                ops.append((line, table[inst])
                           + _SHAPE.search(kind).groups())
    return loops[0], computations, ops


def test_frontier_loop_carries_one_snapshot_row(monkeypatch):
    """What keeps the undo ring from coming back unseen: the frontier
    step's while loop carries ONE N-wide f32 row (the undo snapshot) and no
    (K, N) buffer, and no ``lgbm.bookkeeping`` operation inside the loop
    writes N or more elements, except the snapshot's own window writes (in
    place: a dynamic-update-slice of the row, or a fusion rooted in one).
    Read from the step as the CPU compiles it at toy size, with the
    snapshot's window narrowed so that a window is not the whole row."""
    from lightgbm_tpu.models import learner as learner_mod
    monkeypatch.setattr(learner_mod, "_SNAP_WINDOW", 128)
    K = 4
    bst = _booster({"tpu_frontier_k": K, "max_bin": 15})
    g = bst._gbdt
    Np = g.learner.N_pad
    assert g.learner.plan.frontier_k == K and Np > 4 * 128
    pb, ghi = g._init_phys(g.learner._part0, g.scores, *g._phys_payloads)
    text = g._fused_phys.lower(
        pb, ghi, g._feature_mask(0), 1,
        jnp.zeros((g.learner.F,), bool)).compile().as_text()
    loop, computations, ops = _frontier_loop(text)
    carried = _SHAPE.findall(loop.split(" while(")[0])
    wide = [(t, d) for t, d in carried if d.split(",")[-1] == str(Np)]
    # bins, payload, partition scratch and the snapshot row
    assert sorted(d.count(",") for _, d in wide) == [0, 1, 1, 1], wide
    assert ("f32", str(Np)) in wide
    assert ("f32", f"{K},{Np}") not in wide
    assert not [d for _, d in wide
                if d.count(",") == 1 and d.split(",")[0] == str(K)]

    offenders, window_writes = [], 0
    for line, phase, typ, dims in ops:
        if phase != "bookkeeping" or _elements(dims) < Np:
            continue
        if (typ, dims) == ("f32", str(Np)) \
                and _window_write(line, computations):
            window_writes += 1
        else:
            offenders.append(line.strip()[:160])
    assert window_writes >= 1
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# the benchmark's reader
# ---------------------------------------------------------------------------
def _reader():
    path = os.path.join(ROOT, "benchmark", "readers", "device_phase.py")
    spec = importlib.util.spec_from_file_location("reader_device_phase",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _FakeExecutable:
    """What the registry keeps, as far as it looks: hlo_modules() whose
    members print their text."""

    def __init__(self, text):
        self._text = text

    def hlo_modules(self):
        return [types.SimpleNamespace(to_string=lambda: self._text)]


SCORES_READ = '''HloModule jit_lgbm_scores_read

ENTRY %main.3 (ghi.1: f32[8,128]) -> f32[128] {
  %ghi.1 = f32[8,128]{1,0} parameter(0)
  %copy.12 = f32[8,128]{0,1} copy(%ghi.1), metadata={op_name="jit(lgbm_scores_read)/lgbm.scores_read/scatter"}
  %fusion.8 = f32[128]{0} fusion(%copy.12), kind=kLoop, calls=%fc, metadata={op_name="jit(lgbm_scores_read)/lgbm.scores_read/scatter"}
  ROOT %fusion = f32[128]{0} fusion(%fusion.8), kind=kLoop, calls=%fc2, metadata={op_name="jit(lgbm_scores_read)/lgbm.scores_read/scatter"}
}
'''

PROGRAMS = ["train.fused_step", "train.scores_read"]
GROUPS = {"partition": ["partition", "split_mega"],
          "row_pass": ["gradients", "sampling", "quantize", "leaf_renew",
                       "score_update", "scores_read", "layout_init"],
          "histogram": ["histogram", "hist_state"],
          "split_search": ["search", "bookkeeping"]}


def _ctx(op_seconds, busy=None):
    tree = types.SimpleNamespace(internal_count=np.array([1000, 600, 400]))
    return {"trace": {"op_seconds": op_seconds,
                      "busy_s": busy or sum(op_seconds.values())},
            "traced_trees": [tree, tree], "features": 28, "bin_bytes": 1,
            "chips": 1, "peak": {"hbm_bytes_per_s": 1e9}}


def test_reader_on_a_synthetic_window():
    reader = _reader()
    scopes._reset()
    scopes.register("train.fused_step", _FakeExecutable(SNIPPET))
    scopes.register("train.scores_read", _FakeExecutable(SCORES_READ))
    op_seconds = {
        "lgbm_partition.7": 0.50,            # partition
        "broadcast_select_fusion.15": 0.20,  # bookkeeping, by its body
        "reduce.3": 0.10,                    # search
        "dynamic-update-slice.3": 0.04,      # score_update
        "fusion": 0.06,                      # scores_read: its own name
        "fusion.8": 0.30,      # histogram in the step, scores_read in the
                               # read-back: ambiguous
        "copy.12": 0.03,       # no phase in the step: ambiguous too
        "two_phase_fusion": 0.02,            # no phase
        "custom-call.99": 0.01,              # no program has it
    }
    ctx = _ctx(op_seconds)
    got = {k: reader.read(ctx, "s_per_iter", PROGRAMS, phases=v)
           for k, v in GROUPS.items()}
    assert got == pytest.approx({"partition": 0.25, "row_pass": 0.05,
                                 "histogram": 0.0, "split_search": 0.15})
    share = reader.read(ctx, "unattributed_share", PROGRAMS)
    total = sum(op_seconds.values())
    assert share == pytest.approx(100 * 0.36 / total)
    # the identity the benchmark leans on: the four and the unattributed
    # seconds are the window's operation seconds per iteration
    assert sum(got.values()) + share / 100 * total / 2 == pytest.approx(
        total / 2)
    # least time: 2 trees x 2000 rows x (28 + 8) B at 1e9 B/s, over 0.5 s
    assert reader.read(ctx, "roofline", PROGRAMS,
                       phases=GROUPS["partition"]) == pytest.approx(
        100 * 2 * 2000 * 36 / 1e9 / 0.5)
    # the step alone: its instruction names are ambiguous no more
    assert reader.read(ctx, "s_per_iter", ["train.fused_step"],
                       phases=GROUPS["histogram"]) == pytest.approx(0.15)
    with pytest.raises(ValueError):
        reader.read(ctx, "seconds", PROGRAMS)
    scopes._reset()


def test_reader_without_a_table_reads_finite_values():
    reader = _reader()
    scopes._reset()
    ctx = _ctx({"fusion.1": 0.5, "closed_call.33": 1.5})
    for phases in GROUPS.values():
        assert reader.read(ctx, "s_per_iter", PROGRAMS, phases=phases) == 0.0
    assert reader.read(ctx, "roofline", PROGRAMS,
                       phases=GROUPS["partition"]) == 0.0
    assert reader.read(ctx, "unattributed_share", PROGRAMS) == 100.0


def test_dump_scope_table_names_the_modules(tmp_path):
    import json
    scopes._reset()
    scopes.register("train.fused_step", _FakeExecutable(SNIPPET))
    scopes.dump_scope_table(str(tmp_path / "table.json"))
    doc = json.loads((tmp_path / "table.json").read_text())
    assert doc["modules"] == {"train.fused_step": "jit_lgbm_fused_step"}
    assert doc["tables"]["train.fused_step"]["lgbm_partition.7"] == \
        "partition"
    scopes._reset()


# ---------------------------------------------------------------------------
# the kernel's name on the chip's compiler (no chip: the described topology)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


@contextlib.contextmanager
def _compile_cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _assert_only_kernel(text, name, phase):
    """Every Pallas call of the compiled text is ``name`` under ``phase``."""
    table = scopes.phase_of(text)
    kernels = [n for n in table if n.startswith(name)]
    assert kernels and all(table[n] == phase for n in kernels)
    calls = [ln.split(" = ")[0].split("%")[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and set(calls) == set(kernels)


def _assert_kernel_phases(text, kernels, unnamed=None):
    """Every launch of each of ``kernels`` ({name: phase}) in the compiled
    text is there and under its phase."""
    table = scopes.phase_of(text, unnamed)
    for kernel, phase in kernels.items():
        found = [n for n in table if n.startswith(kernel)]
        assert found and {table[n] for n in found} == {phase}, kernel


def _lower_step(g, one_chip):
    """A booster's fused step lowered for one described chip at the
    learner's own shapes (nothing is placed on the chip)."""
    lr = g.learner

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return g._fused_phys.lower(
        sds((lr._pb_rows, lr.N_pad), jnp.uint8),
        sds((8, lr.N_pad), jnp.float32), sds((lr.F,), jnp.bool_), 1,
        sds((lr.F,), jnp.bool_))


def test_partition_kernel_compiles_for_v5e_under_its_name(one_chip):
    from lightgbm_tpu.ops.partition_pallas import (make_scalars,
                                                   partition_leaf_pallas,
                                                   sc_rows_for)
    C, G32, Np = 2048, 32, 64 * 2048

    def splits(pb, pg, sp):
        def body(i, carry):
            pb, pg, sp = carry
            with scopes.scope("partition"):
                pb, pg, sp, _ = partition_leaf_pallas(
                    pb, pg, sp,
                    make_scalars(C + i, 20 * C, 3, 0, 0, 255, 0, 0, 100, 0),
                    row_chunk=C)
            return pb, pg, sp
        return jax.lax.fori_loop(0, 3, body, (pb, pg, sp))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _compile_cache_off():
        text = jax.jit(splits).lower(
            sds((G32, Np), jnp.uint8), sds((8, Np), jnp.float32),
            sds((sc_rows_for(G32), Np), jnp.int32)).compile().as_text()
    _assert_only_kernel(text, "lgbm_partition", "partition")


@pytest.mark.parametrize("B", [255, 63])
def test_histogram_kernel_compiles_for_v5e_under_its_name(one_chip, B):
    """The cells' geometry (28 features in 32 u8 sublanes, 4096-row
    chunks): what interpret mode cannot show is whether Mosaic takes the
    window DMAs, the bf16 operands and the VMEM the kernel asks for."""
    from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas
    C, G32, Np = 4096, 32, 64 * 4096

    def leaves(pb, pg):
        def body(i, acc):
            with scopes.scope("histogram"):
                return acc + leaf_hist_pallas(
                    pb, pg, C + 37 + i, 20 * C, num_bins=B, row_chunk=C,
                    num_groups=28)
        return jax.lax.fori_loop(0, 3, body,
                                 jnp.zeros((28, B, 2), jnp.float32))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _compile_cache_off():
        text = jax.jit(leaves).lower(
            sds((G32, Np), jnp.uint8),
            sds((8, Np), jnp.float32)).compile().as_text()
    _assert_only_kernel(text, "lgbm_histogram", "histogram")


# the wide shapes: 137 features are five u8 tiles, one pass of the
# partition; 2000 are 63, which the plan cuts into passes of 192 sublanes
# (models/plan.py, PR 35).  What is compiled is the cells' 4096-row chunk
# with seven payload rows live (the cells carry five).
_WIDE = {137: 160, 2000: 2112}


@pytest.mark.parametrize("G", sorted(_WIDE))
def test_partition_kernel_compiles_for_v5e_at_width(one_chip, G):
    from lightgbm_tpu.models import plan
    from lightgbm_tpu.ops import VMEM_LIMIT_BYTES
    from lightgbm_tpu.ops.partition_pallas import (N_SCALARS,
                                                   partition_leaf_pallas,
                                                   pass_rows_for,
                                                   sc_rows_for, vmem_bytes)
    C, Np = 4096, 16 * 4096
    rows = pass_rows_for(G, C, VMEM_LIMIT_BYTES)
    G32 = -(-G // rows) * rows
    assert G32 == _WIDE[G] and vmem_bytes(
        rows, C, passes=G32 // rows) <= VMEM_LIMIT_BYTES

    def split(pb, pg, sp, sc):
        with scopes.scope("partition"):
            return partition_leaf_pallas(pb, pg, sp, sc, row_chunk=C,
                                         ghi_live=7, pass_rows=rows)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _compile_cache_off():
        text = jax.jit(split).lower(
            sds((G32, Np), jnp.uint8), sds((8, Np), jnp.float32),
            sds((sc_rows_for(rows), Np), jnp.int32),
            sds((N_SCALARS,), jnp.int32)).compile().as_text()
    _assert_only_kernel(text, "lgbm_partition", "partition")


@pytest.mark.parametrize("G", sorted(_WIDE))
def test_histogram_kernel_compiles_for_v5e_at_width(one_chip, G):
    from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas
    C, Np, G32 = 4096, 16 * 4096, _WIDE[G]

    def leaf(pb, pg, start, cnt):
        with scopes.scope("histogram"):
            return leaf_hist_pallas(pb, pg, start, cnt, num_bins=255,
                                    row_chunk=C, num_groups=G)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _compile_cache_off():
        compiled = jax.jit(leaf).lower(
            sds((G32, Np), jnp.uint8), sds((8, Np), jnp.float32),
            sds((), jnp.int32), sds((), jnp.int32)).compile()
    _assert_only_kernel(compiled.as_text(), "lgbm_histogram", "histogram")


def _search_at(F, sds):
    from lightgbm_tpu.ops.split_pallas import best_split_pair_pallas

    def pair(hg, hh, fmeta, info):
        with scopes.scope("search"):
            return best_split_pair_pallas(
                hg, hh, fmeta, info, l1=0.0, l2=0.0, max_delta_step=0.0,
                min_gain_to_split=0.0, min_data_in_leaf=1,
                min_sum_hessian=100.0, max_depth=-1)
    return jax.jit(pair).lower(
        sds((2 * F, 256), jnp.float32), sds((2 * F, 256), jnp.float32),
        sds((2 * F, 8), jnp.int32), sds((2 * F, 8), jnp.float32))


def _hist_state_at(G, sds):
    from lightgbm_tpu.ops.hist_state_pallas import (flat_geometry,
                                                    hist_rmw_pallas)
    WL = flat_geometry(G, 255)[2]

    def rmw(state, small, idx):
        with scopes.scope("hist_state"):
            return hist_rmw_pallas(state, small, idx)
    return jax.jit(rmw).lower(
        sds((256, 8, WL), jnp.float32), sds((8, WL), jnp.float32),
        sds((4,), jnp.int32))


# the two kernels that hold everything whole, at 255 bins: the widest
# shape each formula lets the plan name, and the nearest the v5e's
# compiler refuses (the formulas stop a little short of it)
@pytest.mark.parametrize("kernel, width, builds", [
    ("lgbm_split_search", 230, True), ("lgbm_split_search", 234, False),
    ("lgbm_hist_state", 2048, True), ("lgbm_hist_state", 2080, False)])
def test_whole_vmem_kernels_build_as_far_as_their_formulas_say(
        one_chip, kernel, width, builds):
    from lightgbm_tpu.ops import (VMEM_LIMIT_BYTES, hist_state_pallas,
                                  split_pallas)
    need, lower, phase = {
        "lgbm_split_search": (split_pallas.vmem_bytes, _search_at, "search"),
        "lgbm_hist_state": (hist_state_pallas.vmem_bytes, _hist_state_at,
                            "hist_state")}[kernel]
    assert (need(width, 255) <= VMEM_LIMIT_BYTES) == builds

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _compile_cache_off():
        lowered = lower(width, sds)
        if builds:
            _assert_only_kernel(lowered.compile().as_text(), kernel, phase)
        else:
            with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
                lowered.compile()


def test_default_step_at_137_features_compiles_for_v5e(one_chip,
                                                       monkeypatch):
    """The defaults at 137 features under 2^24 rows named a mega-kernel
    the v5e's compiler refused (ISSUE 35).  The plan of (1M, 137, 255) is
    reached at toy rows (3000 take the same 4096-row chunk, and nothing
    else in it follows the rows under 2^24), and the whole fused step
    compiles with the four kernels it names."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models import plan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    X, y = _toy(n=3000, f=137)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 255,
                       "verbosity": -1}, lgb.Dataset(X, label=y))
    g = bst._gbdt
    lr = g.learner
    options = {k: getattr(Config({}), k) for k in plan.OPTION_FIELDS}
    at_size = plan.resolve(plan.PlanFacts(
        backend="tpu", rows=1_000_000, F=137, G=137, B=255, num_leaves=255,
        **options))
    assert at_size.kernel_plan() == {
        "partition": "pallas", "hist": "pallas", "search": "pallas",
        "hist_state": "flat", "mega": "off", "frontier_k": 1}
    assert "lgbm_split_mega would hold" in at_size.why["mega"]
    assert lr.plan.kernel_plan() == at_size.kernel_plan() and lr.B == 255
    assert (lr.plan.row_chunk, lr.plan.pass_rows) \
        == (at_size.row_chunk, at_size.pass_rows) == (4096, 160)

    with _compile_cache_off():
        text = _lower_step(g, one_chip).compile().as_text()
    _assert_kernel_phases(text, {"lgbm_partition": "partition",
                                 "lgbm_histogram": "histogram",
                                 "lgbm_split_search": "search",
                                 "lgbm_hist_state": "hist_state"})
    assert "lgbm_split_mega" not in text


_CONSTANT = re.compile(r"= [a-z]+[0-9]*\[([0-9,]*)\]\S* constant\(")


def test_layout_programs_compile_for_v5e_without_a_row_constant(one_chip):
    """The one-chip layout programs take the objective's payload rows as
    arguments, and XLA:TPU folds none of their rows into a constant: not
    the row ids (an iota masked by the static row bounds), not the padded
    payload.  A row closed over compiled in as an ``N_pad``-element
    constant (168 MB a program at 42M rows)."""
    X, y = _toy(n=3000, f=28)
    g = lgb.Booster({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(X, label=y))._gbdt
    lr = g.learner

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bins = sds((lr._pb_rows, lr.N_pad), jnp.uint8)
    rows = [sds((3000,), jnp.float32) for _ in range(1 + len(
        g._phys_payloads))]
    with _compile_cache_off():
        texts = [g._init_phys_adopt.lower(bins, *rows).compile().as_text(),
                 g._init_phys_perm.lower(
                     bins, sds((lr.N_pad,), jnp.float32), *rows)
                 .compile().as_text()]
    for text in texts:
        assert " iota(" in text
        sizes = [_elements(d) for d in _CONSTANT.findall(text)]
        assert sizes and max(sizes) <= 256, max(sizes)


def _split_loop_copies(text):
    """(the lines of the split loop's body, the ``copy`` instructions the
    loop runs) of a compiled fused step.  The split loop is the one that
    launches the partition kernel, with whatever it calls (outside it the
    compiler may stage a toy buffer into faster memory once a step)."""
    computations = _computations(text)
    body = [c for c, lines in computations.items()
            if any("lgbm_partition" in ln and "custom-call(" in ln
                   for ln in lines)]
    assert len(body) == 1
    return computations[body[0]], [
        line for name in _reachable(computations, body)
        for line in computations[name]
        if re.search(r"[\])}] copy(-start)?\(", line)]


def test_default_step_at_the_cells_facts_compiles_for_v5e_without_a_copy(
        one_chip, monkeypatch):
    """The step every one-chip HIGGS cell runs since ``tpu_frontier_k=auto``
    is 1 (PR 36): the plan of (42M, 28, 255), reached at toy rows through
    path_smooth (the general XLA search, as over 2^24 rows), compiled for
    the described v5e.  Its split loop launches both row kernels and copies
    neither the (8, N) payload, nor the bins, nor the (L + 1, G, B, 2)
    histogram state (the read of the parent's slot, fused into a child's
    write, once kept the old state alive: two copies a split, PR 35)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models import plan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    X, y = _toy(n=3000, f=28)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 255,
                       "verbosity": -1, "path_smooth": 1.0},
                      lgb.Dataset(X, label=y))
    g = bst._gbdt
    lr = g.learner
    options = {k: getattr(Config({}), k) for k in plan.OPTION_FIELDS}
    at_size = plan.resolve(plan.PlanFacts(
        backend="tpu", rows=42_000_000, F=28, G=28, B=255, num_leaves=255,
        **options))
    assert at_size.kernel_plan() == {
        "partition": "pallas", "hist": "pallas", "search": "xla",
        "hist_state": "xla", "mega": "off", "frontier_k": 1}
    assert plan.AUTO_FRONTIER_K in at_size.why["frontier_k"]
    assert lr.plan.kernel_plan() == at_size.kernel_plan() and lr.B == 255
    assert (lr.plan.row_chunk, lr.plan.pass_rows, lr.plan.fast_search) \
        == (at_size.row_chunk, at_size.pass_rows, at_size.fast_search) \
        == (4096, 32, False)
    with _compile_cache_off():
        text = _lower_step(g, one_chip).compile().as_text()
    state = f"f32[{lr.L + 1},{lr.G},{lr.B},2]"
    assert state in text
    loop, copies = _split_loop_copies(text)
    held = re.compile(
        rf"(f32\[8,{lr.N_pad}\]|u8\[{lr._pb_rows},{lr.N_pad}\]|"
        + re.escape(state) + ")")
    for line in copies:
        assert not held.search(line.split(" = ")[1]), line[:200]
    assert any("lgbm_histogram" in ln for ln in loop)
    _assert_kernel_phases(text, {"lgbm_partition": "partition",
                                 "lgbm_histogram": "histogram"})
    calls = {ln.split(" = ")[0].split("%")[-1].split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert calls == {"lgbm_partition", "lgbm_histogram"}


def test_categorical_step_compiles_for_v5e_with_both_kernels(one_chip,
                                                              monkeypatch):
    """The Expo cell's step (benchmark/configs/expo-cat-l255-b255.json: 6
    categorical + 2 numerical columns, 255 leaves and bins) at toy rows,
    compiled for the described v5e: the plan at toy rows is the one at
    44M (categorical features keep the fast search and the mega-kernel
    off at any row count), ``lgbm_partition`` compiles with its
    categorical arm beside ``lgbm_histogram``, and the categorical branch
    of the search is its own phase."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models import plan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = importlib.util.spec_from_file_location(
        "generator_expo", os.path.join(ROOT, "benchmark", "generators",
                                       "expo.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    X, y, kw = gen.make(2**31 + 3, 6000, 0, {})
    bst = lgb.Booster({"objective": "binary", "num_leaves": 255,
                       "max_bin": 255, "verbosity": -1},
                      lgb.Dataset(X, label=y, **kw))
    lr = bst._gbdt.learner
    options = {k: getattr(Config({}), k) for k in plan.OPTION_FIELDS}
    at_size = plan.resolve(plan.PlanFacts(
        backend="tpu", rows=44_000_000, F=8, G=8, B=255, num_leaves=255,
        has_categorical=True, **options))
    assert lr.has_categorical and lr.B == 255
    assert lr.plan.kernel_plan() == at_size.kernel_plan() == {
        "partition": "pallas", "hist": "pallas", "search": "xla",
        "hist_state": "xla", "mega": "off", "frontier_k": 1}
    with _compile_cache_off():
        text = _lower_step(bst._gbdt, one_chip).compile().as_text()
    _assert_kernel_phases(text, {"lgbm_partition": "partition",
                                 "lgbm_histogram": "histogram"})
    assert "search_cat" in set(scopes.phase_of(text).values())


def test_frontier_step_compiles_for_v5e_without_a_payload_copy(
        one_chip, monkeypatch):
    """What the CPU cannot show: XLA:TPU's copy insertion.  The frontier
    step (Pallas partition in place, general XLA search: the plan of the
    benchmark's 42M-row cells, reached at toy size through path_smooth)
    compiled for a described v5e holds no copy of the (8, N) payload or of
    the bins anywhere in its split loop, and its only bookkeeping
    operation there with N or more elements is the snapshot's window
    write.  (The snapshot reads rows that the partition kernel then
    overwrites in place; unordered, the compiler keeps them alive in two
    payload copies per split.)"""
    from lightgbm_tpu.models import learner as learner_mod
    monkeypatch.setattr(learner_mod, "_SNAP_WINDOW", 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bst = _booster({"tpu_frontier_k": 4, "path_smooth": 1.0})
    g = bst._gbdt
    lr = g.learner
    kp = g.kernel_plan()
    assert (kp["partition"], kp["search"], kp["mega"], kp["frontier_k"]) \
        == ("pallas", "xla", "off", 4)
    Np = lr.N_pad
    with _compile_cache_off():
        text = _lower_step(g, one_chip).compile().as_text()
    _, computations, ops = _frontier_loop(text)
    wide_bookkeeping = 0
    for line, phase, typ, dims in ops:
        if dims.split(",")[-1] != str(Np):      # not a row buffer
            continue
        assert not re.search(r"[\])}] copy(-start)?\(", line), line[:200]
        if phase == "bookkeeping":
            assert (typ, dims) == ("f32", str(Np)) \
                and _window_write(line, computations), line[:200]
            wide_bookkeeping += 1
    assert "lgbm_partition" in text
    assert wide_bookkeeping >= 1
    # the leaf histograms are one kernel each, and the step's scope table
    # gives every launch of it (the root's, the smaller children's) the
    # phase the benchmark reads as histogram_s_per_iter
    assert kp["hist"] == "pallas"
    table = scopes.phase_of(text)
    hist = [n for n in table if n.startswith("lgbm_histogram")]
    assert len(hist) >= 2 and {table[n] for n in hist} == {"histogram"}


def _sharded_step(v5e_2x2, monkeypatch, extra=None):
    """(the booster's GBDT, its fused sharded step lowered for the
    described four chips) of a toy tree_learner=data training that reaches
    the four-chip cell's plan through path_smooth.  Nothing is placed on
    the chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cpus = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: cpus)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    X, y = _toy(n=1600)                     # 400 rows a chip: an even cut
    bst = lgb.Booster({"objective": "binary", "num_leaves": 15,
                       "verbosity": -1, "tree_learner": "data",
                       "path_smooth": 1.0, **(extra or {})},
                      lgb.Dataset(X, label=y))
    g = bst._gbdt
    sb, lr = g.sharded_builder, g.learner
    sb.mesh = mesh = Mesh(np.asarray(v5e_2x2.devices), ("data",))
    g._setup_fused_sharded()

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    return g, g._fused_phys.lower(
        sds((lr._pb_rows, 4 * lr.N_pad), jnp.uint8, P(None, "data")),
        sds((8, 4 * lr.N_pad), jnp.float32, P(None, "data")),
        sds((lr.F,), jnp.bool_, P()), 1, sds((lr.F,), jnp.bool_, P()))


def _wide_step(one_chip, monkeypatch, extra=None):
    """The same for one chip at 300 features, 255 leaves and bins: a
    histogram state of 158 MB, over plan.FRONTIER_STATE_MAX_BYTES like the
    wide cell's (whose 2000 features only lengthen the lowering)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    X, y = _toy(n=3000, f=300)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 255,
                       "verbosity": -1, **(extra or {})},
                      lgb.Dataset(X, label=y))
    return bst._gbdt, _lower_step(bst._gbdt, one_chip)


@pytest.mark.parametrize("cell", ["data_parallel", "over_the_state_gate"])
def test_bypass_cells_lower_the_same_step_with_frontier_k_unset_and_1(
        cell, v5e_2x2, one_chip, monkeypatch):
    """The two cells that ran the one-leaf body before ``auto`` became 1
    (PR 36) lower, for the described v5e, byte for byte the step that an
    explicit ``tpu_frontier_k=1`` lowers: a data-parallel learner (an
    explicit K > 1 is refused for its collectives) and a learner whose
    histogram state is over the gate of the batched body."""
    from lightgbm_tpu.models import plan
    def build(extra):
        if cell == "data_parallel":
            return _sharded_step(v5e_2x2, monkeypatch, extra)
        return _wide_step(one_chip, monkeypatch, extra)

    g_auto, auto = build(None)
    g_one, one = build({"tpu_frontier_k": 1})
    lr = g_auto.learner
    assert g_auto.kernel_plan() == g_one.kernel_plan()
    assert (lr.plan.partition, lr.plan.hist, lr.plan.search, lr.plan.mega,
            lr.plan.frontier_k) == ("pallas", "pallas", "xla", "off", 1)
    assert lr.plan.why["frontier_k"] == f"1 ({plan.AUTO_FRONTIER_K})"
    assert g_one.learner.plan.why["frontier_k"] == "1 (tpu_frontier_k=1)"
    if cell == "over_the_state_gate":
        assert (lr.L + 4) * lr.G * lr.B * 8 > plan.FRONTIER_STATE_MAX_BYTES
    text = auto.as_text()
    assert "lgbm_partition" in text and "lgbm_histogram" in text
    assert text == one.as_text()


def test_sharded_step_compiles_for_v5e_2x2_with_both_kernels_in_place(
        v5e_2x2, monkeypatch):
    """tree_learner=data on the described four chips: the fused sharded
    step (the four-chip cell's plan, reached at toy size through
    path_smooth) compiles with shard_map's varying-type check on, runs
    lgbm_partition and lgbm_histogram on each shard, holds its bins only
    with the rows on the lane axis (no (rows, G) u8 buffer, which a TPU
    pads to 128 lanes), copies neither the payload nor the bins beside the
    partition's in-place write, and gives its collectives the phase
    hist_sync."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    g, lowered = _sharded_step(v5e_2x2, monkeypatch)
    sb, lr, mesh = g.sharded_builder, g.learner, g.sharded_builder.mesh
    kp = g.kernel_plan()
    assert (kp["partition"], kp["hist"], kp["search"], kp["frontier_k"],
            kp["tree_learner"]) == ("pallas", "pallas", "xla", 1, "data")
    assert not sb.interpreted_kernels
    Np = lr.N_pad

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    with _compile_cache_off():
        text = lowered.compile().as_text()
        # the read of an even cut: each chip folds its own rows
        read = g._scores_read_sharded.lower(
            sds((8, 4 * Np), jnp.float32, P(None, "data"))
        ).compile().as_text()
    assert "all-" not in read and f"f32[{sb.local_n}]" in read \
        and f"[{4 * sb.local_n}]" not in read
    _assert_kernel_phases(text, {"lgbm_partition": "partition",
                                 "lgbm_histogram": "histogram",
                                 "all-reduce": "hist_sync"}, "hist_sync")
    for d0, d1 in re.findall(r"u8\[(\d+),(\d+)\]", text):
        assert int(d0) * int(d1) < sb.local_n or int(d1) == Np, (d0, d1)
    loop, copies = _split_loop_copies(text)
    for line in copies:
        assert not re.search(rf"(f32\[8|u8\[{lr._pb_rows}),{Np}\]",
                             line.split(" = ")[1]), line[:200]
    assert any("lgbm_histogram" in ln for ln in loop)
