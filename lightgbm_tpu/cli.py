"""Command-line application.

TPU-native re-implementation of the reference CLI (src/main.cpp,
src/application/application.{h,cpp}): `key=value` argv plus a `config=` file,
tasks train | predict | convert_model | refit | save_binary, plus two
framework-native tasks: `continual` — a deterministic drift drill
through the continual-training runtime (lightgbm_tpu/continual/):
drift is injected at a chosen tick, the regression must be detected, a
background retrain (killed once and resumed from checkpoint) hot-swaps
in, and a forced post-swap regression rolls back — the operator's
rehearsal that every continual failure path works on THIS install;
and `serve` — the production serving plane (lightgbm_tpu/serving/):
coalescing micro-batcher, multi-model registry with hot-swap/rollback,
per-tenant admission control, stdlib HTTP.

Usage:  python -m lightgbm_tpu task=train config=train.conf [key=value ...]
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

import numpy as np

from .basic import Booster, Dataset
from .config import Config
from .engine import train as _train
from .utils import log
from .utils.textio import load_text_file

__all__ = ["Application", "main"]


def parse_config_file(path: str) -> Dict[str, str]:
    """Parse a reference-format config file: `key = value` lines, `#` comments
    (reference: application.cpp Application::LoadParameters / ConfigFile)."""
    out: Dict[str, str] = {}
    with open(path) as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def parse_argv(argv: List[str]) -> Dict[str, str]:
    """reference: application.cpp Application(argc, argv):31-86 — argv
    `key=value` pairs override config-file values."""
    cli: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            log.warning("Unknown argument (expected key=value): %s", arg)
            continue
        k, v = arg.split("=", 1)
        cli[k.strip()] = v.strip()
    params: Dict[str, str] = {}
    if "config" in cli:
        params.update(parse_config_file(cli["config"]))
    params.update(cli)  # command line overrides config file
    return params


class Application:
    """reference: src/application/application.h Application."""

    def __init__(self, argv: List[str]):
        self.raw_params = parse_argv(argv)
        self.config = Config(self.raw_params)

    def run(self) -> None:
        task = self.config.task
        # runtime telemetry (lightgbm_tpu/obs/): telemetry=counters|trace
        # arms the session before any device work; telemetry_out=DIR
        # exports JSONL + Chrome trace + Prometheus text when the task
        # finishes (even when it fails — the trace of a failed run is
        # the artifact an operator wants most)
        from . import obs
        obs.configure_from_config(self.config)
        # multi-host bootstrap before any device work (reference:
        # application.cpp:171 Network::Init ahead of LoadData/Train)
        from .parallel.network import init_from_config
        init_from_config(self.config)
        from .parallel.distributed import sync_config_params
        sync_config_params(self.config)
        try:
            if task == "train":
                self.train()
            elif task in ("predict", "prediction", "test"):
                self.predict()
            elif task == "convert_model":
                self.convert_model()
            elif task == "refit":
                self.refit()
            elif task == "save_binary":
                self.save_binary()
            elif task == "continual":
                self.continual()
            elif task == "serve":
                self.serve()
            else:
                log.fatal("Unknown task: %s", task)
        finally:
            if self.config.telemetry_out and obs.enabled():
                # never let a failed export mask the task's own error
                # (e.g. an unwritable telemetry_out during a training
                # failure must not replace the training exception)
                try:
                    obs.memory_snapshot()
                    paths = obs.export_session(self.config.telemetry_out)
                    log.info("telemetry exported: %s",
                             ", ".join(sorted(paths.values())))
                except OSError as exc:
                    log.warning("telemetry export to %s failed: %s",
                                self.config.telemetry_out, exc)

    # ------------------------------------------------------------------
    @staticmethod
    def _side_file(path: str, suffix: str):
        """Reference-style side files next to the data file
        (dataset_loader.cpp LoadQueryBoundaries / LoadWeights /
        LoadInitialScore: ``<data>.query`` etc.)."""
        import numpy as np
        p = path + "." + suffix
        if os.path.exists(p):
            return np.loadtxt(p, dtype=np.float64, ndmin=1)
        return None

    def _load_train_data(self) -> Dataset:
        cfg = self.config
        if not cfg.data:
            log.fatal("No training data file specified (data=)")
        from .dataset import BinnedDataset
        if BinnedDataset.is_binary_file(cfg.data):
            # binary fast path (reference: LoadFromBinFile,
            # dataset_loader.cpp:417)
            return Dataset(cfg.data, params=dict(self.raw_params))
        loaded = load_text_file(
            cfg.data, has_header=cfg.header, label_column=cfg.label_column,
            weight_column=cfg.weight_column, group_column=cfg.group_column,
            ignore_column=cfg.ignore_column)
        group = loaded.group
        if group is None:
            group = self._side_file(cfg.data, "query")
        weight = loaded.weight
        if weight is None:
            weight = self._side_file(cfg.data, "weight")
        init = self._side_file(cfg.data, "init")
        ds = Dataset(loaded.X, label=loaded.label, weight=weight,
                     group=group, init_score=init,
                     feature_name=loaded.feature_names or "auto",
                     params=dict(self.raw_params))
        return ds

    def train(self) -> None:
        cfg = self.config
        train_set = self._load_train_data()
        valid_sets: List[Dataset] = []
        valid_names: List[str] = []
        if cfg.valid:
            for i, vf in enumerate(str(cfg.valid).split(",")):
                vf = vf.strip()
                if not vf:
                    continue
                vl = load_text_file(
                    vf, has_header=cfg.header, label_column=cfg.label_column,
                    weight_column=cfg.weight_column,
                    group_column=cfg.group_column,
                    ignore_column=cfg.ignore_column)
                vgroup = vl.group if vl.group is not None \
                    else self._side_file(vf, "query")
                vweight = vl.weight if vl.weight is not None \
                    else self._side_file(vf, "weight")
                valid_sets.append(Dataset(
                    vl.X, label=vl.label, weight=vweight, group=vgroup,
                    init_score=self._side_file(vf, "init"),
                    reference=train_set, params=dict(self.raw_params)))
                valid_names.append(os.path.basename(vf))
        init_model = cfg.input_model or None
        callbacks = None
        if cfg.snapshot_freq and cfg.snapshot_freq > 0:
            # periodic model snapshots (reference: GBDT::Train,
            # gbdt.cpp:244-248 — "<output_model>.snapshot_iter_<i>"),
            # written atomically (temp + rename) so a crash mid-write
            # never leaves a truncated model file behind
            freq = int(cfg.snapshot_freq)
            out_path = cfg.output_model

            def _snapshot(env):
                it = env.iteration + 1
                if it % freq == 0:
                    final = f"{out_path}.snapshot_iter_{it}"
                    tmp = f"{final}.tmp{os.getpid()}"
                    env.model.save_model(tmp)
                    os.replace(tmp, final)

            _snapshot.order = 100
            callbacks = [_snapshot]
        if cfg.checkpoint_dir and not cfg.checkpoint_interval:
            log.warning("checkpoint_dir is set but checkpoint_interval is "
                        "0; no training checkpoints will be written (set "
                        "checkpoint_interval=N to checkpoint every N "
                        "iterations)")
        if cfg.checkpoint_resume:
            log.info("checkpoint_resume=true: will resume from the latest "
                     "checkpoint under %s if one exists", cfg.checkpoint_dir)
        if cfg.is_provide_training_metric:
            # reference: training_metric adds the train set to the
            # evaluated sets (Application::LoadData train_metric path)
            valid_sets = [train_set] + valid_sets
            valid_names = ["training"] + valid_names
        if valid_sets or cfg.is_provide_training_metric:
            # periodic metric output every metric_freq iterations
            # (reference: Application::Train -> Boosting::Train
            # OutputMetric cadence, config.h metric_freq)
            from .callback import log_evaluation
            callbacks = (callbacks or []) + [
                log_evaluation(period=max(int(cfg.metric_freq), 1))]
        booster = _train(dict(self.raw_params), train_set,
                         num_boost_round=cfg.num_iterations,
                         valid_sets=valid_sets or None,
                         valid_names=valid_names or None,
                         init_model=init_model,
                         callbacks=callbacks)
        booster.save_model(cfg.output_model)
        log.info("Finished training; model saved to %s", cfg.output_model)
        # model/data-health artifact (obs/health.py): the flight
        # recorder + reference profile + skew digests of THIS run,
        # next to the telemetry exports
        from .obs import health as obs_health
        if obs_health.enabled() and cfg.telemetry_out:
            import json as _json
            try:
                os.makedirs(cfg.telemetry_out, exist_ok=True)
                out = os.path.join(cfg.telemetry_out, "health.json")
                with open(out, "w") as fh:
                    _json.dump(booster.health_report(), fh, indent=1,
                               default=str)
                log.info("health report exported: %s", out)
            except OSError as exc:
                log.warning("health export to %s failed: %s",
                            cfg.telemetry_out, exc)

    def predict(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            log.fatal("task=predict requires input_model=")
        if not cfg.data:
            log.fatal("task=predict requires data=")
        booster = Booster(model_file=cfg.input_model)
        loaded = load_text_file(
            cfg.data, has_header=cfg.header, label_column=cfg.label_column,
            ignore_column=cfg.ignore_column)
        preds = booster.predict(
            loaded.X, raw_score=bool(cfg.predict_raw_score),
            pred_leaf=bool(cfg.predict_leaf_index),
            pred_contrib=bool(cfg.predict_contrib),
            start_iteration=int(cfg.start_iteration_predict),
            num_iteration=cfg.num_iteration_predict,
            predict_disable_shape_check=bool(
                cfg.predict_disable_shape_check),
            pred_early_stop=bool(cfg.pred_early_stop),
            pred_early_stop_freq=int(cfg.pred_early_stop_freq),
            pred_early_stop_margin=float(cfg.pred_early_stop_margin))
        preds = np.asarray(preds)
        with open(cfg.output_result, "w") as fh:
            if preds.ndim == 1:
                fh.write("\n".join(repr(float(v)) for v in preds))
            else:
                fh.write("\n".join("\t".join(repr(float(v)) for v in row)
                                   for row in preds))
            fh.write("\n")
        log.info("Finished prediction; results saved to %s", cfg.output_result)

    def refit(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            log.fatal("task=refit requires input_model=")
        booster = Booster(model_file=cfg.input_model)
        loaded = load_text_file(
            cfg.data, has_header=cfg.header, label_column=cfg.label_column,
            weight_column=cfg.weight_column, group_column=cfg.group_column,
            ignore_column=cfg.ignore_column)
        extra = {k: v for k, v in self.raw_params.items()
                 if k not in ("task", "config", "data", "input_model",
                              "output_model", "valid")}
        new_booster = booster.refit(loaded.X, loaded.label,
                                    weight=loaded.weight, group=loaded.group,
                                    decay_rate=cfg.refit_decay_rate, **extra)
        new_booster.save_model(cfg.output_model)
        log.info("Finished refit; model saved to %s", cfg.output_model)

    def continual(self) -> None:
        """Run the deterministic continual-training drift drill (see the
        module docstring) with this config's ``continual_*`` parameters;
        one JSON line per scenario, non-zero exit on a broken invariant.
        ``checkpoint_dir=`` roots the retrain checkpoints (a temp
        directory otherwise)."""
        import json
        import shutil
        import tempfile

        from .continual import run_drift_drill

        cfg = self.config
        work = cfg.checkpoint_dir or tempfile.mkdtemp(prefix="continual-")
        own_tmp = not cfg.checkpoint_dir
        # the drill's synthetic stream is regression-shaped; IO/model
        # params don't apply to it
        _skip = {"task", "config", "objective", "num_class", "data",
                 "valid", "input_model", "output_model", "metric"}
        overrides = {k: v for k, v in self.raw_params.items()
                     if Config.canonical_name(k) is not None
                     and Config.canonical_name(k) not in _skip}
        problems = []
        try:
            for scenario in ("swap", "degrade", "rollback"):
                rep = run_drift_drill(
                    scenario, params=overrides,
                    checkpoint_dir=work if scenario == "swap" else None)
                rep.pop("ticks", None)
                print(json.dumps({"scenario": scenario, "report": {
                    k: v for k, v in rep.items() if k != "history"}}))
                if scenario == "swap" and not (
                        rep.get("detected_within_window")
                        and rep.get("one_trace_per_key")
                        and rep.get("swap_tick") is not None):
                    problems.append("swap drill failed")
                if scenario == "degrade" and not rep.get("still_serving"):
                    problems.append("degrade drill failed")
                if scenario == "rollback" and not (
                        rep.get("rollback_within")
                        and rep.get("pre_post_identical")):
                    problems.append("rollback drill failed")
        finally:
            if own_tmp:
                shutil.rmtree(work, ignore_errors=True)
        if problems:
            log.fatal("continual drill: %s", "; ".join(problems))
        log.info("continual drill passed: detection, checkpointed "
                 "retrain, guarded swap, degradation and rollback all "
                 "exercised")

    def serve(self) -> None:
        """Run the production serving plane (lightgbm_tpu/serving/):
        coalescing micro-batcher over the device ServingEngine,
        multi-model registry with hot-swap/rollback endpoints, and
        per-tenant admission control, behind a stdlib HTTP server.
        Models: ``serve_models=name=path[,...]`` or ``input_model=``
        (published as ``default``); see the ``serve_*`` parameter
        family and README "Serving service"."""
        from .serving.httpd import run_serve_task
        run_serve_task(self.config)

    def save_binary(self) -> None:
        cfg = self.config
        ds = self._load_train_data()
        ds.construct(dict(self.raw_params))
        out = cfg.data + ".bin"
        ds.save_binary(out)
        log.info("Saved binary dataset to %s", out)

    def convert_model(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            log.fatal("task=convert_model requires input_model=")
        language = cfg.convert_model_language or "cpp"
        if language not in ("cpp", "c++"):
            log.fatal("Only convert_model_language=cpp is supported")
        booster = Booster(model_file=cfg.input_model)
        code = model_to_cpp(booster)
        with open(cfg.convert_model, "w") as fh:
            fh.write(code)
        log.info("Converted model written to %s", cfg.convert_model)


def model_to_cpp(booster: Booster) -> str:
    """Generate standalone C++ if-else prediction code from a model
    (reference: gbdt_model_text.cpp GBDT::ModelToIfElse)."""
    g = booster._gbdt
    K = g.num_tree_per_iteration
    out: List[str] = [
        "// Generated by lightgbm_tpu task=convert_model",
        "#include <cmath>",
        "#include <cstring>",
        "",
        f"static const int kNumClass = {g.num_class};",
        f"static const int kNumTreePerIteration = {K};",
        f"static const int kMaxFeatureIdx = {g.max_feature_idx};",
        "",
    ]

    def emit_node(tree, nid: int, depth: int, lines: List[str]) -> None:
        ind = "  " * depth
        if nid < 0:
            leaf = ~nid
            lines.append(f"{ind}return {float(tree.leaf_value[leaf])!r};")
            return
        f = int(tree.split_feature[nid])
        cat, default_left, _missing = tree.unpack_decision_type(
            int(tree.decision_type[nid]))
        if cat:
            cats = tree.cat_threshold_values(nid) \
                if hasattr(tree, "cat_threshold_values") else []
            cond = " || ".join(f"fval == {c}.0" for c in cats) or "false"
            lines.append(f"{ind}{{ const double fval = arr[{f}];")
            lines.append(f"{ind}if (!std::isnan(fval) && ({cond})) {{")
        else:
            thr = float(tree.threshold[nid])
            lines.append(f"{ind}{{ const double fval = arr[{f}];")
            if default_left:
                lines.append(
                    f"{ind}if (std::isnan(fval) || fval <= {thr!r}) {{")
            else:
                lines.append(
                    f"{ind}if (!std::isnan(fval) && fval <= {thr!r}) {{")
        emit_node(tree, int(tree.left_child[nid]), depth + 1, lines)
        lines.append(f"{ind}}} else {{")
        emit_node(tree, int(tree.right_child[nid]), depth + 1, lines)
        lines.append(f"{ind}}} }}")

    for i, tree in enumerate(g.models):
        out.append(f"static double PredictTree{i}(const double* arr) {{")
        body: List[str] = []
        if tree.num_leaves <= 1:
            body.append(f"  return {float(tree.leaf_value[0])!r};")
        else:
            emit_node(tree, 0, 1, body)
        out.extend(body)
        out.append("}")
        out.append("")

    out.append("void Predict(const double* features, double* output) {")
    out.append(f"  for (int k = 0; k < kNumTreePerIteration; ++k) "
               f"output[k] = 0.0;")
    for i in range(len(g.models)):
        out.append(f"  output[{i % K}] += PredictTree{i}(features);")
    out.append("}")
    out.append("")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    app = Application(argv)
    app.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
