"""Serving launcher: continuous-batching LLM inference on any assigned
architecture (reduced variants by default, for CPU runs; ``--no-reduced``
serves the published widths, e.g. on a TPU).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --engine paged --requests 8 --max-new 16

``--engine paged`` (default for pure-attention stacks) runs the
block-paged engine with admission-aware scheduling; ``--engine slot``
runs the fixed-slot baseline.  ``--prefix-cache on`` (the default)
shares previously computed prompt-prefix blocks across requests via the
radix tree in ``serving/prefix_cache.py``.  ``--decode-kernel on``
routes paged decode attention through the Pallas paged-attention kernel
(auto = on when kernels are globally enabled: TPU or
``REPRO_USE_KERNELS=1``); ``--prefill-buckets`` pads prefill shapes to
length buckets so mixed-length traffic compiles O(#buckets) prefill
variants ("auto" = powers of two, "off" = exact shapes, or an explicit
"8,16,64" list).  ``--scheduler continuous`` (default) admits every
admissible request per step and drains prompt prefills as
``--prefill-chunk``-token chunks under a ``--step-token-budget`` cap so
running decodes keep advancing every step; ``--scheduler serial`` is
the one-admission-per-step whole-prompt baseline.
``--spec-decode ngram`` turns on speculative decoding with zero-weight
prompt-lookup drafting (``--spec-k`` drafted tokens per request per
step, verified in the fused ragged dispatch, token-identical to
``off``); ``--spec-decode draft`` drafts with an early-exit truncation
of the target (its first ``--draft-layers`` layers — no extra weights).
``--decode-fusion off`` reverts spec-off decode to the separate decode
program instead of riding the fused ragged dispatch as length-1 verify
windows.
``--replicas N`` (paged engine only) serves through the cluster tier
(``serving/cluster.py``): N broker-fed engine replicas behind the
occupancy-aware balancer, with ``--affinity on`` (default) routing each
request to the replica already holding its longest cached prefix;
saturation rejects submissions with 429 semantics instead of queueing
unboundedly.
Queue/pool/prefix-cache/compile gauges are printed every
``--stats-every`` steps and at exit.  ``--metrics`` dumps the full
Prometheus text exposition at exit (with ``--replicas`` the per-replica
registries merged into one fleet page); ``--trace-out PATH`` writes a
Chrome trace-event JSON of the run (open in https://ui.perfetto.dev).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models.api import Model
from repro.obs import Observability
from repro.serving.cluster import Rejected, ServingCluster
from repro.serving.server import LLMEngine, PagedLLMEngine
from repro.serving.spec_decode import layer_truncated_draft


def _fmt_stats(stats: dict) -> str:
    """Render the stats-schema gauges (``serving/stats_schema.py``) or,
    for balancer snapshots (``LoadBalancer.stats()``), the dispatch
    counters.  Every key goes through ``.get()`` — stats dicts from
    older engines or persisted snapshots may omit newer gauges."""
    if "replica_loads" in stats:
        line = (f"[lb] picks={stats.get('picks', 0)} "
                f"rejections={stats.get('rejections', 0)} "
                f"releases={stats.get('releases', 0)} "
                f"imbalance={stats.get('imbalance', 0.0):.2f} "
                f"loads={stats.get('replica_loads', [])}")
        if isinstance(stats.get("engine"), dict):
            line += "\n" + _fmt_stats(stats["engine"])
        for rid, es in sorted(stats.get("engines", {}).items()):
            line += f"\n  r{rid} " + _fmt_stats(es)
        return line
    if stats.get("engine") == "cluster":
        return (f"[cluster] replicas={stats.get('replicas', 0)} "
                f"affinity={'on' if stats.get('affinity') else 'off'} "
                f"hits={stats.get('affinity_hits', 0)} "
                f"misses={stats.get('affinity_misses', 0)} "
                f"429={stats.get('rejected_429', 0)} "
                f"submitted={stats.get('submitted', 0)} "
                f"finished={stats.get('finished', 0)}")
    line = (f"[{stats.get('engine', '?')}] "
            f"queue={stats.get('queue_depth', 0)} "
            f"active={stats.get('active', 0)} "
            f"blocks={stats.get('used_blocks', 0)}"
            f"/{stats.get('total_blocks', 0)} "
            f"occ={stats.get('pool_occupancy', 0.0):.2f} "
            f"preempt={stats.get('preemptions', 0)} "
            f"finished={stats.get('finished', 0)} "
            f"compiles={stats.get('prefill_compiles', 0)}"
            f"p/{stats.get('decode_compiles', 0)}d")
    if stats.get("prefix_cache"):
        line += (f" hit={stats.get('hit_rate', 0.0):.2f} "
                 f"cached={stats.get('cached_blocks', 0)} "
                 f"evict={stats.get('evictions', 0)}")
    if stats.get("window_blocks_freed"):
        line += f" wfreed={stats.get('window_blocks_freed', 0)}"
    if stats.get("state_slots_used"):
        line += f" slots={stats.get('state_slots_used', 0)}"
    return line


def build_engine(args, model, params, obs=None):
    if args.engine == "paged":
        buckets = args.prefill_buckets
        if buckets not in ("auto", "off"):
            buckets = [int(b) for b in buckets.split(",")]
        kernel = {"auto": None, "on": True, "off": False}[args.decode_kernel]
        draft_model = draft_params = None
        if args.spec_decode == "draft":
            draft_model, draft_params = layer_truncated_draft(
                model, params, args.draft_layers)
        return PagedLLMEngine(model, params, num_blocks=args.num_blocks,
                              block_size=args.block_size,
                              max_batch=args.max_batch,
                              max_len=args.cache_max,
                              prefix_cache=args.prefix_cache == "on",
                              prefill_buckets=buckets,
                              decode_kernel=kernel,
                              scheduler=args.scheduler,
                              prefill_chunk=args.prefill_chunk,
                              step_token_budget=args.step_token_budget,
                              spec_decode=args.spec_decode,
                              spec_k=args.spec_k,
                              draft_model=draft_model,
                              draft_params=draft_params,
                              decode_fusion=args.decode_fusion == "on",
                              window_accounting=args.window_accounting
                              == "on",
                              obs=obs)
    if args.spec_decode != "off":
        raise SystemExit("--spec-decode needs the paged engine")
    return LLMEngine(model, params, num_slots=args.slots,
                     cache_max=args.cache_max, obs=obs)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="CPU-smoke widths (default); --no-reduced builds "
                         "the published widths")
    ap.add_argument("--engine", choices=("paged", "slot"), default=None,
                    help="default: paged when the arch supports it")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="on",
                    help="radix-tree block reuse across shared prompt "
                         "prefixes (paged engine only)")
    ap.add_argument("--decode-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="Pallas paged-attention decode kernel vs jnp "
                         "block gather (auto: follow the global kernel "
                         "switch; paged engine only)")
    ap.add_argument("--prefill-buckets", default="auto",
                    help="prefill length bucketing: auto (powers of two), "
                         "off (exact shapes), or a comma list like "
                         "8,16,64 (paged engine only)")
    ap.add_argument("--scheduler", choices=("continuous", "serial"),
                    default="continuous",
                    help="continuous: multi-admission + chunked prefill "
                         "interleaved with decode; serial: one whole-"
                         "prompt admission per step (paged engine only)")
    ap.add_argument("--prefill-chunk", type=int, default=256,
                    help="prompt tokens per prefill chunk (snapped to a "
                         "length bucket and capped by --cache-max)")
    ap.add_argument("--step-token-budget", type=int, default=None,
                    help="max prompt tokens prefilled per engine step "
                         "(default: one chunk)")
    ap.add_argument("--spec-decode", choices=("off", "ngram", "draft"),
                    default="off",
                    help="speculative decoding: ngram = prompt-lookup "
                         "drafting (zero extra weights), draft = early-"
                         "exit layer truncation of the target; output "
                         "stays token-identical to off (paged engine, "
                         "continuous scheduler only)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max drafted tokens per request per step")
    ap.add_argument("--decode-fusion", choices=("on", "off"), default="on",
                    help="run spec-off decode through the fused ragged "
                         "dispatch as length-1 verify windows — one XLA "
                         "program per step (paged engine, continuous "
                         "scheduler only)")
    ap.add_argument("--window-accounting", choices=("on", "off"),
                    default="on",
                    help="eagerly free KV blocks that slide out of a "
                         "bounded attention window (sliding-window "
                         "stacks; off = window-blind block accounting, "
                         "the capacity baseline)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through the cluster tier with N broker-"
                         "fed engine replicas (paged engine only)")
    ap.add_argument("--affinity", choices=("on", "off"), default="on",
                    help="prefix-affinity routing: send each request to "
                         "the replica already holding its longest cached "
                         "prefix (cluster tier only)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="layers kept in the --spec-decode draft "
                         "truncation")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-max", type=int, default=128,
                    help="per-request cache strip (slot) / max_len (paged)")
    ap.add_argument("--stats-every", type=int, default=16)
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus text exposition at exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(open in Perfetto)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def load_model(args):
    """-> (cfg, model, params) for the parsed CLI ``args``; resolves the
    default ``--engine`` for the arch.  Weights are random from
    ``--seed``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.frontend != "none" or cfg.is_encoder_decoder:
        raise SystemExit(f"{cfg.name}: serve CLI drives text-only decode; "
                         "use examples/serve_digits.py for the full app")
    model = Model(cfg)
    if args.engine is None:
        args.engine = "paged" if model.supports_paged else "slot"
    params = model.init(jax.random.PRNGKey(args.seed))
    return cfg, model, params


def main():
    args = build_parser().parse_args()
    configure_compile_cache()
    cfg, model, params = load_model(args)
    if args.replicas > 1:
        if args.engine != "paged":
            raise SystemExit("--replicas needs the paged engine")
        if args.trace_out:
            raise SystemExit("--trace-out is per-engine; not supported "
                             "with --replicas")
        _serve_cluster(args, cfg, model, params)
        return
    obs = None
    if args.metrics or args.trace_out:
        obs = Observability.create(trace=args.trace_out is not None)
    engine = build_engine(args, model, params, obs=obs)

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size,
                              size=(args.prompt_len,)).astype(np.int32)
        engine.submit(prompt, max_new=args.max_new, now=time.time() - t0)

    finished = []
    steps = 0
    while not engine.idle:
        finished.extend(engine.step(now=time.time() - t0))
        steps += 1
        if args.stats_every and steps % args.stats_every == 0:
            print(_fmt_stats(engine.stats()))
    dt = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in finished)
    print(f"{len(finished)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s, {steps} engine steps, "
          f"engine={args.engine})")
    print(_fmt_stats(engine.stats()))
    for r in finished[:3]:
        print(f"  req {r.rid}: {len(r.out_tokens)} tokens "
              f"{r.out_tokens[:8]}...")
    if obs is not None and args.trace_out:
        n = obs.trace.export(args.trace_out)
        print(f"trace: {n} events -> {args.trace_out}")
    if obs is not None and args.metrics:
        print(obs.metrics.render(), end="")


def _serve_cluster(args, cfg, model, params):
    """Drive ``--requests`` prompts through the multi-replica cluster
    tier: a shared-prefix-flavoured workload (half the prompt is one of
    a few tenant prefixes) so ``--affinity on`` has something to route
    on; saturation surfaces as counted 429s, never a stall."""
    cluster = ServingCluster(
        lambda i: build_engine(args, model, params),
        args.replicas, affinity=args.affinity == "on",
        seed=args.seed, obs=args.metrics)
    rng = np.random.default_rng(args.seed)
    tenants = [rng.integers(1, cfg.vocab_size,
                            max(args.prompt_len // 2, 1)).astype(np.int32)
               for _ in range(min(4, args.requests))]
    t0 = time.time()
    rejected = 0
    for i in range(args.requests):
        tail = rng.integers(1, cfg.vocab_size,
                            size=(max(args.prompt_len
                                      - len(tenants[0]), 1),))
        prompt = np.concatenate([tenants[i % len(tenants)],
                                 tail.astype(np.int32)])
        try:
            cluster.submit(prompt, max_new=args.max_new,
                           now=time.time() - t0)
        except Rejected:
            rejected += 1
    finished = []
    steps = 0
    while not cluster.idle:
        finished.extend(cluster.step(now=time.time() - t0))
        steps += 1
        if args.stats_every and steps % args.stats_every == 0:
            print(_fmt_stats(cluster.stats()))
    dt = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in finished)
    print(f"{len(finished)} requests ({rejected} rejected 429), "
          f"{total_new} tokens in {dt:.2f}s ({total_new/dt:.1f} tok/s, "
          f"{steps} cluster steps, replicas={args.replicas})")
    print(_fmt_stats(cluster.stats()))
    print(_fmt_stats(cluster.balancer.stats()))
    for r in sorted(finished, key=lambda r: r.cid)[:3]:
        print(f"  req {r.cid}: {len(r.out_tokens)} tokens "
              f"{r.out_tokens[:8]}... r{r.replica} via {r.routed_by}")
    if args.metrics:
        print(cluster.merged_metrics().render(), end="")


if __name__ == "__main__":
    main()
