"""Record the small profiler trace that ``tests/test_chipbench_devtrace.py``
checks the reduction against: a few engine steps of a two-layer model on
the chip, inside the harness's own host spans.

    python3 chipbench/record_trace.py   # on the chip; writes testdata/
"""
from __future__ import annotations

import dataclasses
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE / "testdata" / "tiny_step.xplane.pb"


def main():
    sys.path.insert(0, str(HERE.parent / "src"))
    import jax
    import numpy as np

    import devtrace
    import weights
    from repro.configs.base import get_config
    from repro.models.api import Model
    from repro.serving.server import PagedLLMEngine

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=2)
    model = Model(cfg)
    params = weights.make_params(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))), 0)
    eng = PagedLLMEngine(model, params, num_blocks=65, max_batch=4,
                         max_len=256, prefix_cache=True)
    rng = np.random.default_rng(0)
    for _ in range(4):
        eng.submit(rng.integers(1, cfg.vocab_size, 40), max_new=6)
    while not eng.idle:                      # compile every shape first
        eng.step()
    for _ in range(4):
        eng.submit(rng.integers(1, cfg.vocab_size, 40), max_new=6)
    tmp = HERE / "out" / "record_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        while not eng.idle:
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
            with jax.profiler.TraceAnnotation("harness.account"):
                sum(range(20000))
    jax.profiler.stop_trace()
    OUT.parent.mkdir(exist_ok=True)
    shutil.copy(devtrace.find_xplane(str(tmp)), OUT)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(OUT)).planes:
        print(plane.name, [(ln.name, len(list(ln.events))) for ln in plane.lines])
    print(devtrace.reduce_dir(str(tmp)))
    print(f"{OUT}: {OUT.stat().st_size} bytes")


if __name__ == "__main__":
    main()
