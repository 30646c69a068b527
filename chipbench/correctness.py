"""Whether what the timed path served is what the model computes.

After the window, a sample of the finished requests, drawn from the seed
and always holding the longest, is run once through the configuration's
plain float32 reference over prompt + served tokens.  For each served
token the gap is how far its reference logit lies below the reference's
best logit at that position (0 when the served token is the reference's
own first choice).  The compared number is the mean gap over every
served token of the sample: the widest gap, a single near-tie of a
random model's top logits, reads as high in sound bfloat16 runs as a
third of the float8 control's, so it cannot tell the two apart, while
the mean can.  The limit is in the configuration file, with the readings
it was set from in PERF.md.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
SERVED_TARGET = 512     # served tokens the sample holds at least (if any)


def load_reference(cfg: dict):
    path = HERE / "configs" / f"{cfg['reference']}.py"
    spec = importlib.util.spec_from_file_location(cfg["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(tracks, seed: int) -> list:
    """Finished requests that emitted a token at or after the window's
    start; the longest (prompt + served) first, then others in an order
    drawn from ``seed`` until SERVED_TARGET served tokens are held."""
    done = [t for t in tracks if t.done and t.stamps and t.stamps[-1] >= 0.0]
    if not done:
        return []
    done.sort(key=lambda t: t.req.idx)
    longest = max(done, key=lambda t: len(t.req.prompt) + len(t.gen.out_tokens))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    picked, served = [longest], len(longest.gen.out_tokens)
    for i in rng.permutation(len(rest)):
        if served >= SERVED_TARGET:
            break
        picked.append(rest[i])
        served += len(rest[i].gen.out_tokens)
    return picked


def verdict(got: dict, limit: float) -> bool:
    """``correct``: some served token was compared, and the mean gap is
    within the limit."""
    return got["served"] > 0 and got["logit_gap_mean"] <= limit


def compare(ref, weights, cfg, picked, control=False) -> dict:
    """-> {"logit_gap_mean": mean gap, "logit_gap_max": widest gap,
    "served": tokens compared} and, with ``control``, "control_gap_mean"
    and "control_gap_max": the same of the float8 model's first choices
    at the same positions."""
    gaps, cgaps = [], []
    for t in picked:
        g, c = ref.served_gaps(weights, cfg, t.req.prompt, t.gen.out_tokens,
                               control=control)
        gaps.append(g)
        if control:
            cgaps.append(c)
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    out = {"logit_gap_mean": float(g.mean()) if len(g) else float("inf"),
           "logit_gap_max": float(g.max()) if len(g) else float("inf"),
           "served": int(len(g))}
    if control:
        c = np.concatenate(cgaps)
        out["control_gap_mean"] = float(c.mean()) if len(c) else float("inf")
        out["control_gap_max"] = float(c.max()) if len(c) else float("inf")
    return out
