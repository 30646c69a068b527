"""Smoke run of the system's main paths on one TPU chip.

    python chip_smoke.py

One process, in order:

1. device   -- require a TPU (no CPU fallback, no interpret mode);
2. serving  -- qwen3-0.6b at its published widths in bfloat16, random
               weights from a seed, through the serve CLI's own
               ``load_model`` / ``build_engine`` with the engine defaults
               (continuous scheduler, fused decode, prefix cache, auto
               prefill buckets): 8 requests of 512 tokens sharing a
               256-token prefix, 32 new tokens each, run twice;
3. kernels  -- the four Pallas kernels compiled at the widths of the
               configs that use them, each against its ``kernels/ref.py``
               reference, then the engine once more with the separate
               decode program on the paged-attention kernel;
4. digits   -- the paper's CNN pipeline: train a few rounds, predict at
               batch 1 and 32.

Any failed check exits non-zero before the last line.  On success the
last line of stdout is ``{"ok": true, "device": {...}}``.  Wall times
printed on the way are smoke readings that include compilation, not
benchmark numbers.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
SERVE_ARGV = ["--arch", "qwen3-0.6b", "--no-reduced", "--engine", "paged",
              "--requests", "8", "--prompt-len", "512", "--max-new", "32",
              "--cache-max", "1024", "--num-blocks", "1025",
              "--block-size", "16", "--seed", str(SEED)]
# Largest error a kernel may show against its reference, as a share of
# the reference's largest magnitude.  bfloat16 attention: ~8 bits of
# mantissa on inputs and output; float32 scan and conv: accumulation
# order only.
KERNEL_TOL = {"paged_attention": 2e-2, "flash_attention": 2e-2,
              "rwkv6_scan": 1e-3, "conv2d": 1e-3}


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ device


def device_phase():
    """The first TPU, or SmokeFailure.  Runs before any model work."""
    for var in ("REPRO_FORCE_PALLAS_INTERPRET", "REPRO_USE_KERNELS"):
        check(var not in os.environ,
              f"{var} is set: the smoke run takes the compiled kernel path "
              "only; unset it")
    import jax

    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU: JAX found no device ({e})") from None
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    from repro.kernels.ops import kernel_path_active

    check(kernel_path_active(), "kernel path inactive on a TPU")
    log(f"device: {dev.device_kind} x{len(jax.devices())} "
        f"jax {jax.__version__}")
    return dev


# ------------------------------------------------------------ serving


def make_prompts(n: int, prompt_len: int, vocab: int, seed: int):
    """``n`` prompts whose first half is one shared prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, prompt_len // 2)
    return [np.concatenate([prefix, rng.integers(
                1, vocab, prompt_len - len(prefix))]).astype(np.int32)
            for _ in range(n)]


def serve_once(argv, model, params, prompts):
    """Build the engine as the serve CLI does, run ``prompts`` to the
    end.  -> (tokens per prompt in submit order, stats, wall seconds)."""
    import jax

    from repro.launch.serve import build_engine, build_parser

    args = build_parser().parse_args(argv)
    engine = build_engine(args, model, params)
    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new=args.max_new) for p in prompts]
    out = {}
    for _ in range(100 * (len(prompts) + args.max_new)):
        for r in engine.step(now=time.perf_counter() - t0):
            out[r.rid] = list(r.out_tokens)
        if engine.idle:
            break
    jax.block_until_ready(engine.pools)
    wall = time.perf_counter() - t0
    check(engine.idle and len(out) == len(prompts),
          f"{len(out)} of {len(prompts)} requests finished")
    return [out[r] for r in rids], engine.stats(), wall


def serving_phase(argv):
    """The main path.  -> (argv, model, params, prompts, tokens)."""
    from repro.launch.serve import build_parser, load_model

    args = build_parser().parse_args(argv)
    t0 = time.perf_counter()
    cfg, model, params = load_model(args)
    log(f"serving: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.resolved_head_dim} "
        f"vocab={cfg.vocab_size} {cfg.dtype}; init "
        f"{time.perf_counter() - t0:.2f}s")
    prompts = make_prompts(args.requests, args.prompt_len, cfg.vocab_size,
                           args.seed)
    toks, stats, wall = serve_once(argv, model, params, prompts)
    flat = [t for seq in toks for t in seq]
    check(all(len(t) == args.max_new for t in toks),
          f"output lengths {[len(t) for t in toks]} != {args.max_new}")
    check(all(0 <= t < cfg.vocab_size for t in flat),
          "token outside the vocabulary")
    check(stats["hit_rate"] > 0, f"prefix cache hit_rate {stats['hit_rate']}")
    log(f"serving: {len(toks)} requests x {args.max_new} tokens, "
        f"hit_rate={stats['hit_rate']:.3f} "
        f"compiles={stats['prefill_compiles']}p/{stats['decode_compiles']}d; "
        f"wall {wall:.2f}s (smoke reading incl. compile, not a benchmark)")
    again, _, wall2 = serve_once(argv, model, params, prompts)
    check(again == toks, "second run with the same seed emitted other tokens")
    log(f"serving: rerun token-identical; wall {wall2:.2f}s (smoke reading)")
    return model, params, prompts, toks


def peak_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


# ------------------------------------------------------------ kernels


def _compare(name: str, got, want) -> None:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
          f"{want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want)) /
                max(float(np.max(np.abs(want))), 1e-30))
    log(f"kernels: {name} {tuple(got.shape)} max err {err:.2e} of max "
        f"|ref| (tol {KERNEL_TOL[name]:.0e})")
    check(err <= KERNEL_TOL[name], f"{name}: error {err:.3e} over tolerance")


def _paged_case(rng, b, h, kv, hd, num_blocks, bs, nb):
    """Random pools; each row holds a random-length run of blocks, with
    unwritten tail lanes at pos -1 and the table 0-padded (null block)."""
    import jax.numpy as jnp
    import numpy as np

    k_pool = rng.standard_normal((num_blocks, bs, kv, hd), np.float32)
    v_pool = rng.standard_normal((num_blocks, bs, kv, hd), np.float32)
    pos_pool = np.full((num_blocks, bs), -1, np.int32)
    bt = np.zeros((b, nb), np.int32)
    pos = np.zeros((b,), np.int32)
    free = list(rng.permutation(np.arange(1, num_blocks)))
    for i in range(b):
        length = int(rng.integers(1, nb * bs + 1))
        for j in range(-(-length // bs)):
            blk = bt[i, j] = free.pop()
            lanes = np.arange(bs) + j * bs
            pos_pool[blk, lanes < length] = lanes[lanes < length]
        pos[i] = length - 1
    q = rng.standard_normal((b, h, hd), np.float32)
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(k_pool, jnp.bfloat16),
            jnp.asarray(v_pool, jnp.bfloat16), jnp.asarray(pos_pool),
            jnp.asarray(bt), jnp.asarray(pos))


def kernels_phase(widths: dict, seed: int = SEED) -> None:
    """Each Pallas kernel through ``kernels/ops`` against its reference
    (run at highest matmul precision).  ``widths``: ``attn`` (B, H, KV,
    hd, S, pool blocks, block size, table cols), ``rwkv`` (B, H, S, hd),
    ``conv`` batches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    b, h, kv, hd, s, num_blocks, bs, nb = widths["attn"]

    def ref_at_highest(fn, *a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)

    args = _paged_case(rng, b, h, kv, hd, num_blocks, bs, nb)
    _compare("paged_attention", ops.paged_attention(*args),
             ref_at_highest(ref.paged_attention_ref, *args))

    q, k, v = (jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)
               for shape in ((1, h, s, hd), (1, kv, s, hd), (1, kv, s, hd)))
    _compare("flash_attention", ops.flash_attention(q, k, v, causal=True),
             ref_at_highest(ref.flash_attention_ref, q, k, v, causal=True))

    rb, rh, rs, rhd = widths["rwkv"]
    r, kk, vv = (jnp.asarray(rng.standard_normal((rb, rh, rs, rhd),
                                                 np.float32))
                 for _ in range(3))
    w = jnp.asarray(np.exp(-np.exp(rng.standard_normal(
        (rb, rh, rs, rhd)).astype(np.float32) * 0.5 - 1.0)))
    u = jnp.asarray(rng.standard_normal((rh, rhd), np.float32) * 0.1)
    out, sfin = ops.rwkv6_scan(r, kk, vv, w, u)
    want_out, want_s = ref_at_highest(ref.rwkv6_scan_ref, r, kk, vv, w, u)
    _compare("rwkv6_scan", out, want_out)
    _compare("rwkv6_scan", sfin, want_s)

    wconv = jnp.asarray(rng.standard_normal((3, 3, 1, 32), np.float32) / 3)
    for cb in widths["conv"]:
        x = jnp.asarray(rng.random((cb, 28, 28, 1), np.float32))
        _compare("conv2d", ops.conv2d(x, wconv),
                 ref_at_highest(ref.conv2d_ref, x, wconv))


def decode_kernel_phase(argv, model, params, prompts, toks) -> None:
    """The engine with the separate decode program on the Pallas
    paged-attention kernel."""
    argv = argv + ["--decode-fusion", "off", "--decode-kernel", "on"]
    got, stats, wall = serve_once(argv, model, params, prompts)
    check(stats["decode_kernel"] == 1,
          f"decode_kernel gauge reads {stats['decode_kernel']}")
    same = sum(a == b for x, y in zip(got, toks) for a, b in zip(x, y))
    total = sum(len(x) for x in toks)
    # not gated: a bfloat16 argmax over a random 151,936-way vocabulary
    # can flip on near-ties between the two attention reads
    log(f"kernels: decode-kernel engine {len(got)} requests, "
        f"decode_compiles={stats['decode_compiles']}; tokens agreeing "
        f"with fused decode {same}/{total}; wall {wall:.2f}s "
        f"(smoke reading)")


# ------------------------------------------------------------ digits


def digits_phase(rounds: int = 8, train_n: int = 4_000) -> None:
    """The paper's pipeline: distributed-strategy CNN training, then the
    deployed predict function."""
    import numpy as np

    from repro.core.pipeline import StratusPipeline

    pipe = StratusPipeline(strategy="sync", num_workers=5, seed=SEED)
    t0 = time.perf_counter()
    hist = pipe.train(train_n=train_n, rounds=rounds)["history"]
    losses = [h["loss"] for h in hist]
    check(np.isfinite(losses).all() and losses[-1] < losses[0],
          f"digit loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    predict = pipe.predict_fn()
    rng = np.random.default_rng(SEED)
    for b in (1, 32):
        probs = predict(rng.random((b, 28, 28, 1), np.float32))
        check(probs.shape == (b, 10), f"predict shape {probs.shape}")
        check(np.allclose(probs.sum(-1), 1.0, atol=1e-3),
              "probabilities do not sum to 1")
    log(f"digits: {rounds} rounds, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; predict at batch 1 and 32; wall "
        f"{time.perf_counter() - t0:.2f}s (smoke reading)")


# ------------------------------------------------------------ main


def main() -> int:
    try:
        dev = device_phase()
        from repro.launch.compile_cache import configure_compile_cache

        log(f"compile cache: {configure_compile_cache()}")
        model, params, prompts, toks = serving_phase(SERVE_ARGV)
        log(f"serving: peak device memory {peak_memory(dev)}")
        kernels_phase({
            # qwen3-0.6b attention; rwkv6-1.6b heads; mnist-cnn batches
            "attn": (8, 16, 8, 128, 512, 1025, 16, 64),
            "rwkv": (1, 32, 256, 64),
            "conv": (1, 32),
        })
        decode_kernel_phase(SERVE_ARGV, model, params, prompts, toks)
        digits_phase()
        log(f"peak device memory {peak_memory(dev)}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
