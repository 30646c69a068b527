"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process: it refuses anything but a TPU, makes the weights on the
device from the seed, builds the engine through the serve launcher's
``build_engine``, warms up the step shapes the cell's traffic will use
(``warmup.py``), pre-rolls the traffic, measures for ``--seconds``, checks
a sample of what was served against the plain reference
(``correctness.py``), and prints as its last line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``) and, last, ``compared``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer ones; both are read by the files in
``metrics/`` named after them.

Everything cell-specific is found by name: ``BENCHMARK.json`` names the
cell's configuration (``configs/<name>.json``) and traffic mix
(``traffic/<name>.json``), and each metric is ``metrics/<name>.py``.
"""
from __future__ import annotations

import time

T_SCRIPT = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = HERE / "out" / "trace"


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since the script
    began."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / ticks
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return max(uptime - start, time.perf_counter() - T_SCRIPT)
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_SCRIPT


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Cell:
    """A cell of BENCHMARK.json with its configuration, mix and metrics."""

    def __init__(self, name: str, bench: dict = None, cfg: dict = None):
        import traffic

        bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.spec = cells[name]
        self.cfg = cfg or json.loads((HERE / "configs" /
                                      f"{self.spec['config']}.json").read_text())
        self.mix = traffic.load_mix(self.spec["traffic"])
        self.e2e = [m for m in bench["end_to_end"] if name in
                    m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"] if name in
                          m.get("workloads", [name])]

    @property
    def cache_max(self) -> int:
        import traffic

        return traffic.max_context(self.mix)


def read_metric(name: str, run):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def require_tpu(chips: int):
    """-> the devices; exits (no result) off a TPU or with too few chips."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def check_config(cfg: dict, pc) -> None:
    """The program's registered configuration must be the file's."""
    want = {"num_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "resolved_head_dim": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"], "rope_theta": float(cfg["rope_theta"]),
            "tie_embeddings": cfg["tie_word_embeddings"], "qk_norm": cfg["qk_norm"],
            "dtype": cfg["torch_dtype"], "mlp_kind": "swiglu",
            "norm_kind": "rmsnorm", "pos_kind": "rope", "qkv_bias": False,
            "num_experts": 0, "kv_cache_quant": False}
    got = {k: getattr(pc, k) for k in want}
    if pc.kinds_for_layers != ("attn",) * cfg["num_hidden_layers"]:
        got["layer_kinds"] = pc.kinds_for_layers
        want["layer_kinds"] = "all attn"
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise SystemExit(f"program config differs from {cfg['arch']}.json: {bad}")


class Session:
    """Model, weights and engine of one cell in this process."""

    def __init__(self, cell: Cell, seed: int, log=print):
        from repro.configs.base import get_config
        from repro.launch.serve import build_engine, build_parser
        from repro.models.api import Model

        import jax
        import weights

        self.cell = cell
        cfg = cell.cfg
        pc = get_config(cfg["arch"])
        check_config(cfg, pc)
        self.model = Model(pc)
        t0 = time.perf_counter()
        self.params = weights.make_params(
            jax.eval_shape(lambda: self.model.init(jax.random.PRNGKey(0))), seed)
        jax.block_until_ready(self.params)
        log(f"weights: {time.perf_counter() - t0:.3f} s")
        eng = cfg["engine"]
        self.args = build_parser().parse_args([
            "--arch", cfg["arch"], "--no-reduced", "--engine", "paged",
            "--num-blocks", str(eng["num_blocks"]),
            "--block-size", str(eng["block_size"]),
            "--max-batch", str(eng["max_batch"]),
            "--cache-max", str(cell.cache_max), "--seed", str(seed)])
        self._build = build_engine
        self.engine = self.make_engine()

    def make_engine(self, pool: bool = True):
        """A fresh engine; ``pool=False`` allocates no block pool (for the
        warm-up's stubbed replay)."""
        if pool:
            return self._build(self.args, self.model, self.params)
        self.model.pool_init = lambda *a, **k: None
        try:
            return self._build(self.args, self.model, self.params)
        finally:
            del self.model.pool_init


class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    def __init__(self):
        import jax

        self.on, self.traces, self.compiles = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if not self.on:
            return
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def drive(session: Session, reqs, seconds: float, trace: bool, setup_t0: float):
    """Pre-roll, window and (open loop) drain.  -> (RunLog, stats at the
    window's start and close, obs events or None, devtrace dict, setup_s,
    compile counter)."""
    import jax

    import devtrace
    from feed import Feeder, WallClock
    from repro.obs import Observability

    cell, eng = session.cell, session.engine
    obs = None
    if trace:
        obs = Observability.create(trace=True)
        eng.attach_obs(obs)
    counter = CompileCounter()
    annotate = jax.profiler.TraceAnnotation if trace else \
        (lambda _n: contextlib.nullcontext())
    origin = time.perf_counter() + float(cell.mix["preroll_s"])
    setup_s = origin - setup_t0
    clock = WallClock(origin)
    drv = Feeder(eng, cell.mix, reqs, clock, annotate)
    state = {"stats0": None, "stats1": None, "span": None, "tracing": False}
    # the profiler starts half-way through the pre-roll and stops once the
    # run is over, so neither call stalls the window; the traced window
    # is marked by its own host span
    trace_from = -float(cell.mix["preroll_s"]) / 2

    def hook(now):
        if trace and not state["tracing"] and now >= trace_from:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            state["tracing"] = True
        if now >= 0.0 and state["stats0"] is None:
            state["stats0"] = eng.stats()
            counter.on = True
            # whatever compiles inside the window is named on stderr
            jax.config.update("jax_log_compiles", True)
            if trace:
                state["span"] = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
                state["span"].__enter__()
        if now >= seconds and state["stats1"] is None:
            counter.on = False
            jax.config.update("jax_log_compiles", False)
            state["stats1"] = eng.stats()
            if state["span"] is not None:
                state["span"].__exit__(None, None, None)

    drv.hooks.append(hook)
    runlog = drv.run(seconds)
    hook(max(runlog.end, seconds))
    if state["tracing"]:
        jax.profiler.stop_trace()
    dt = devtrace.reduce_dir(str(TRACE_DIR)) if state["tracing"] else {}
    events = obs.trace.to_chrome()["traceEvents"] if obs else None
    return runlog, state["stats0"], state["stats1"], events, dt, setup_s, counter


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devs,
             peaks: dict, setup_t0: float, say=print,
             control: bool = False) -> dict:
    """Set up, measure and check one run.  -> the result object (with
    ``control``, its ``compared`` also holds the float8 control's mean
    gap, and ``widest`` both widest gaps)."""
    import jax
    import numpy as np

    import correctness
    import traffic
    import warmup
    import weights
    from runview import RunView

    session = Session(cell, seed, log=say)
    vocab = cell.cfg["vocab_size"]
    reqs = traffic.generate(cell.mix, seed, vocab, seconds)
    t0 = time.perf_counter()
    sigs = warmup.warm(session.engine, session.make_engine, cell.mix, reqs,
                       seconds, cell.cfg["step_cost_ms"], log=say)
    say(f"warm-up: {len(sigs)} signatures in {time.perf_counter() - t0:.2f} s")

    runlog, s0, s1, events, dt, setup_s, counter = drive(
        session, reqs, seconds, trace, setup_t0)
    steps = [s for s in runlog.steps if 0.0 <= s.t0 < seconds]
    say(f"window: {len(steps)} steps, compiles inside: {counter.traces} "
        f"traces, {counter.compiles} backend compiles")
    warmed = set(sigs)
    outside = sorted({s[:3] for s in session.engine._prefill_sigs} - warmed)
    say(f"dispatch signatures outside the warm-up: {outside}")
    if steps:
        top = max(steps, key=lambda s: s.t1 - s.t0)
        say(f"longest step: {(top.t1 - top.t0) * 1e3:.1f} ms at {top.t0:.3f} s")
    late = np.asarray(runlog.late_s) if runlog.late_s else np.zeros((1,))
    say(f"generator lateness: p50 {np.percentile(late, 50) * 1e3:.3f} ms, "
        f"max {late.max() * 1e3:.3f} ms over {len(runlog.late_s)} submissions")

    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    say(f"memory: peak {device['memory_peak_bytes']} of "
        f"{stats.get('bytes_limit', 0)} bytes")
    if trace:
        device["busy_s"] = dt.get("busy_s", 0.0)
        device["window_s"] = dt.get("window_s", 0.0)

    due = [t for t in runlog.tracks if 0.0 <= t.due < seconds]
    refused = [r for r in runlog.refused if 0.0 <= r.due < seconds]
    attempted = len(due) + len(refused)
    failed = len(refused) + sum(1 for t in due if not t.stamps)

    run = RunView(runlog, seconds, cell.cfg, peaks, s0, s1, events, dt,
                  setup_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.e2e):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the reference runs once the program's state is freed
    picked = correctness.sample(runlog.tracks, seed)
    del session.engine, run
    gc.collect()
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        got = correctness.compare(correctness.load_reference(cell.cfg),
                                  weights.reference_view(session.params),
                                  cell.cfg, picked, control=control)
    limit = cell.cfg["correctness"]["logit_gap_mean"]
    say(f"reference: {len(picked)} requests, {got['served']} served tokens "
        f"in {time.perf_counter() - t0:.2f} s; widest gap "
        f"{got['logit_gap_max']!r}")
    result = {"correct": correctness.verdict(got, limit),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if trace and dt:
        result["breakdown"] = {"device_ops": dt["device_ops"],
                               "idle_gaps": dt["idle_gaps"]}
    if control:
        result["widest"] = {"program": got["logit_gap_max"],
                            "control": got["control_gap_max"]}
    result["compared"] = {
        "logit_gap_mean": {"value": got["logit_gap_mean"], "limit": limit},
        "served_tokens": {"value": got["served"], "limit": 1}}
    if control:
        result["compared"]["control_gap_mean"] = {
            "value": got["control_gap_mean"], "limit": limit}
    result["_runlog"] = runlog
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    setup_t0 = time.perf_counter() - process_age_s()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs outside
    sys.path.insert(0, str(ROOT / "src"))
    cell = Cell(args.workload)
    devs = require_tpu(cell.spec["chips"])

    import jax

    from peaks import peaks_for
    from repro.launch.compile_cache import configure_compile_cache

    def say(msg):
        print(msg, flush=True)

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = peaks_for(devs[0].device_kind)
    say(f"cell {cell.name}: {devs[0].device_kind} x{len(devs)}, "
        f"cache {cache_dir}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, setup_t0, say)
    result.pop("_runlog")
    cmp = result["compared"]
    print(f"compared logit_gap_mean={cmp['logit_gap_mean']['value']!r} "
          f"limit={cmp['logit_gap_mean']['limit']!r} (at most)",
          file=sys.stderr)
    print(f"compared served_tokens={cmp['served_tokens']['value']} "
          f"limit=1 (at least)", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
