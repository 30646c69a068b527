"""The plain reference against the program's own model, on logits, at a
reduced size on the CPU (float32 weights, both configurations'
families)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correctness
import weights


def _cfg_dict(pc):
    return {"hidden_size": pc.d_model, "intermediate_size": pc.d_ff,
            "num_hidden_layers": pc.num_layers,
            "num_attention_heads": pc.num_heads,
            "num_key_value_heads": pc.num_kv_heads,
            "head_dim": pc.resolved_head_dim, "vocab_size": pc.vocab_size,
            "rms_norm_eps": 1e-6, "rope_theta": pc.rope_theta,
            "qk_norm": pc.qk_norm, "tie_word_embeddings": pc.tie_embeddings,
            "reference": "dense_reference"}


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "phi4-mini-3.8b"])
def test_reference_matches_program_logits(arch):
    from repro.configs.base import get_config
    from repro.models.api import Model

    pc = get_config(arch).reduced()
    model = Model(pc)
    params = weights.make_params(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))), 17)
    tokens = np.random.default_rng(0).integers(1, pc.vocab_size, 300)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": jnp.asarray(tokens[None])},
                               remat=False)
        cfg = _cfg_dict(pc)
        ref = correctness.load_reference(cfg)
        view = weights.reference_view(params)
        h = ref.final_hidden(view, cfg, tokens)[:len(tokens)]
        want = jnp.einsum("sd,vd->sv", h, view["embed"].astype(jnp.float32))
    got = np.asarray(got[0], np.float64)
    want = np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert scale > 0.1
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_served_gaps_are_zero_for_the_reference_s_own_greedy_tokens():
    from repro.configs.base import get_config
    from repro.models.api import Model

    pc = get_config("qwen3-0.6b").reduced()
    model = Model(pc)
    params = weights.make_params(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))), 4)
    cfg = _cfg_dict(pc)
    ref = correctness.load_reference(cfg)
    view = weights.reference_view(params)
    prompt = np.random.default_rng(1).integers(1, pc.vocab_size, 20)
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(6):     # greedy continuation by the reference itself
            h = ref.final_hidden(view, cfg, np.asarray(seq))[len(seq) - 1]
            seq.append(int(jnp.argmax(view["embed"].astype(jnp.float32) @ h)))
        gaps, _ = ref.served_gaps(view, cfg, prompt, seq[len(prompt):])
        wrong = [(t + 1) % pc.vocab_size for t in seq[len(prompt):]]
        bad, _ = ref.served_gaps(view, cfg, prompt, wrong)
    assert len(gaps) == 6 and np.all(gaps < 1e-5)
    assert np.all(bad > 1e-3)
