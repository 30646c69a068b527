"""The traffic generator: seeded, clipped, and the same requests at the
same times whatever the seed."""
import numpy as np
import pytest

import traffic

MIX = "chat-prefix"
SECONDS = 50


def _gen(seed, seconds=SECONDS):
    return traffic.generate(traffic.load_mix(MIX), seed, 151936, seconds)


def test_same_seed_same_requests():
    a, b = _gen(2**31 + 11), _gen(2**31 + 11)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    c = _gen(5)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_lengths_within_clips():
    mix = traffic.load_mix(MIX)
    sys_len = mix["tenants"]["system_prompt_tokens"]
    for seed in range(4):
        for r in _gen(seed, 400):
            body = len(r.prompt) - sys_len
            assert mix["prompt"]["min"] <= body <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
            assert len(r.prompt) + r.max_new <= traffic.max_context(mix)
            assert r.prompt.min() >= 1


@pytest.mark.parametrize("seeds", [(1, 2), (3, 2**33 + 7)])
def test_every_segment_holds_the_same_work(seeds):
    mix = traffic.load_mix(MIX)
    a, b = (_gen(s) for s in seeds)
    assert len(a) == len(b) == sum(n for _, _, n in
                                   traffic.segments(mix, SECONDS))
    for start, length, n in traffic.segments(mix, SECONDS):
        sa = [r for r in a if start <= r.due < start + length]
        sb = [r for r in b if start <= r.due < start + length]
        assert len(sa) == len(sb) == n
        assert sorted((len(r.prompt), r.max_new, r.tenant) for r in sa) == \
            sorted((len(r.prompt), r.max_new, r.tenant) for r in sb)
        assert sum(np.diff([start] + [r.due for r in sa])) < length
    # the seed draws only the tokens: the same requests at the same times
    assert [r.due for r in a] == [r.due for r in b]
    assert [(len(r.prompt), r.max_new, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.tenant) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_window_holds_rate_times_seconds():
    mix = traffic.load_mix(MIX)
    due = [r.due for r in _gen(8)]
    assert sum(0 <= d < SECONDS for d in due) == \
        round(mix["rate_per_s"] * SECONDS)
    assert due == sorted(due) and due[0] >= -mix["preroll_s"]


def test_chat_prompts_share_their_tenant_prefix():
    by_tenant = {}
    for r in _gen(3):
        by_tenant.setdefault(r.tenant, []).append(r.prompt[:1024])
    assert len(by_tenant) > 1
    for prefixes in by_tenant.values():
        assert all(np.array_equal(p, prefixes[0]) for p in prefixes)
