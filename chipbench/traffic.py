"""The one traffic generator: open-loop Poisson arrivals.  A mix is a data
file in ``traffic/``:

    rate_per_s   offered load, requests per second
    preroll_s    seconds of traffic before the window opens
    drain_s      how long past the close to wait for the first token of
                 requests due in the window
    size_seed    seed of the fixed sizes, tenants and gaps
    tenants      null, or {count, zipf_s, system_prompt_tokens}: every
                 prompt starts with its tenant's shared system prompt
    prompt       {median, sigma, min, max}: lognormal user-turn length
    output       {median, sigma, min, max}: lognormal output length

A run's timeline has three segments: the pre-roll ``[-preroll_s, 0)``,
the window ``[0, seconds)`` and the drain ``[seconds, seconds +
drain_s)``.  Each segment holds ``round(rate_per_s x length)`` requests
whose lengths, tenants and gaps are drawn from ``size_seed`` alone, the
gaps scaled to fill the segment exactly (a Poisson process given its
count), in the order they are drawn.  ``--seed`` draws only the token
contents (system prompts and user turns), so every seed sends the same
requests at the same times: with some tens of requests to a window, the
order alone decides which requests overlap, and with it how many rows
wait through each prefill chunk.  (The lognormal length and
tenant-prefix arithmetic follows
``src/repro/serving/loadgen.py:multi_tenant_workload``.)
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Request:
    idx: int
    due: float            # seconds from the window's start (< 0: pre-roll)
    prompt: np.ndarray    # (S,) int32
    max_new: int
    tenant: int           # -1 without a shared prefix


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def max_context(mix: dict) -> int:
    """The longest prompt plus the longest output the mix can send."""
    sys_len = mix["tenants"]["system_prompt_tokens"] if mix["tenants"] else 0
    return sys_len + mix["prompt"]["max"] + mix["output"]["max"]


def segments(mix: dict, seconds: float) -> list:
    """-> [(start, length, request count)] of pre-roll, window and drain."""
    out, t = [], -float(mix["preroll_s"])
    for length in (float(mix["preroll_s"]), float(seconds),
                   float(mix["drain_s"])):
        out.append((t, length, int(round(mix["rate_per_s"] * length))))
        t += length
    return out


def _lognormal(rng, spec, n):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def _fixed_segment(mix: dict, index: int, length: float, n: int):
    """Lengths, outputs, tenants and gaps of one segment, from
    ``size_seed`` alone."""
    fixed = np.random.default_rng([mix["size_seed"], index])
    lens = _lognormal(fixed, mix["prompt"], n)
    outs = _lognormal(fixed, mix["output"], n)
    ten = mix["tenants"]
    if ten:
        ranks = np.arange(1, ten["count"] + 1, dtype=np.float64)
        p = ranks ** -float(ten["zipf_s"])
        tenants = fixed.choice(ten["count"], size=n, p=p / p.sum())
    else:
        tenants = np.full((n,), -1)
    gaps = fixed.exponential(1.0, n + 1)
    return lens, outs, tenants, gaps * (length / gaps.sum())


def generate(mix: dict, seed: int, vocab: int, seconds: float) -> list:
    """The requests of one run of ``mix`` for ``seed``, in due order."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    ten = mix["tenants"]
    prefixes = [rng.integers(1, vocab, ten["system_prompt_tokens"],
                             dtype=np.int64).astype(np.int32)
                for _ in range(ten["count"])] if ten else []
    reqs = []
    for k, (start, length, n) in enumerate(segments(mix, seconds)):
        lens, outs, tenants, gaps = _fixed_segment(mix, k, length, n)
        due = start + np.cumsum(gaps)[:n]
        for i in range(n):
            idx = len(reqs)
            body = rng.integers(1, vocab, int(lens[i]), dtype=np.int64)
            body[0] = 1 + (idx % (vocab - 1))   # distinct lead token: no accidental sharing
            t = int(tenants[i])
            prompt = body.astype(np.int32) if t < 0 else \
                np.concatenate([prefixes[t], body.astype(np.int32)])
            reqs.append(Request(idx, float(due[i]), prompt, int(outs[i]), t))
    return reqs
