"""The check that decides ``correct``, driven through a whole run of the
tiny cell on the CPU (the harness's look for a chip skipped): it passes
the program as it is, and fails it with a served token altered where it
is produced, and with the float8 control in the program's place."""
import jax
import numpy as np
import pytest

import correctness
import tiny
import weights

# The tiny cell's own limit on the mean gap, set like the chip cells'
# (PERF.md): sound runs read at most 5e-5 (seeds 3-5), the float8 control
# at least 0.0076 and a run with altered tokens at least 0.088, all on a
# CPU at this size.
TINY_LIMIT = 0.002


@pytest.fixture(scope="module")
def sound():
    with pytest.MonkeyPatch.context() as mp:
        yield tiny.run(mp, limit=TINY_LIMIT)


def test_sound_run_is_correct(sound):
    res = sound
    assert res["correct"], res["compared"]
    assert res["compared"]["served_tokens"]["value"] >= 100
    assert list(res)[-2:] == ["compared", "_runlog"]


def test_altered_token_fails(monkeypatch):
    from repro.serving.server import PagedLLMEngine

    real = PagedLLMEngine._ragged_dispatch
    calls = [0]

    def altered(self, rows, state_rows=None, *, all_logits):
        out = real(self, rows, state_rows, all_logits=all_logits)
        calls[0] += 1
        if calls[0] % 5:
            return out
        arr = np.array(out)              # one greedy id, altered where made
        arr[0, len(rows[0][0]) - 1] = (arr[0, len(rows[0][0]) - 1] + 1) \
            % self.model.cfg.vocab_size
        return arr

    monkeypatch.setattr(PagedLLMEngine, "_ragged_dispatch", altered)
    res = tiny.run(monkeypatch, limit=TINY_LIMIT)
    assert not res["correct"]
    assert res["compared"]["logit_gap_mean"]["value"] > TINY_LIMIT


def test_float8_control_fails(monkeypatch, sound):
    picked = correctness.sample(sound["_runlog"].tracks, 3)
    cell = tiny.cell(monkeypatch, limit=TINY_LIMIT)
    from repro.models.api import Model

    model = Model(tiny.program_config())
    params = weights.make_params(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))), 3)
    with jax.default_matmul_precision("highest"):
        got = correctness.compare(correctness.load_reference(cell.cfg),
                                  weights.reference_view(params), cell.cfg,
                                  picked, control=True)
    assert correctness.verdict(got, TINY_LIMIT)
    control = dict(got, logit_gap_mean=got["control_gap_mean"])
    assert not correctness.verdict(control, TINY_LIMIT)
    assert got["control_gap_mean"] > TINY_LIMIT
