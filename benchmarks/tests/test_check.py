"""The output check's control: the reference computed in int8, put in
the program's place, has to read wider than a sound program does. Kept
at a size a test run can hold (a 4-layer, 256-wide bf16 model on the
CPU); the readings that set each cell's limit were taken on the chip at
the cell's own size (PERF.md section 2)."""

import numpy as np
import pytest

from harness import check
from references import llama_dense

CFG = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
       "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": False}


def greedy_bf16(params, prompt, n):
    """A sound bf16 'program': the same architecture with bf16
    activations, decoding greedily (no cache: the whole sequence again
    for every token)."""
    import jax
    import jax.numpy as jnp
    s = llama_dense.sizes(CFG)

    @jax.jit
    def logits_at(tokens, pos):
        x = params["embed"][tokens]
        n_tok = x.shape[0]

        def norm(x, w):
            xf = x.astype(jnp.float32)
            return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                       + 1e-5) * w[None, :]).astype(jnp.bfloat16)

        def layer(x, lp):
            h = norm(x, lp["attn_norm"])
            q = llama_dense._rope((h @ lp["wq"]).reshape(
                n_tok, s["Hq"], s["hd"]).astype(jnp.float32), 10000.0)
            k = llama_dense._rope((h @ lp["wk"]).reshape(
                n_tok, s["Hkv"], s["hd"]).astype(jnp.float32), 10000.0)
            v = (h @ lp["wv"]).reshape(n_tok, s["Hkv"], s["hd"])
            k = jnp.repeat(k.astype(jnp.bfloat16), 2, 1)
            v = jnp.repeat(v, 2, 1)
            sc = jnp.einsum("qhd,khd->hqk", q.astype(jnp.bfloat16), k
                            ).astype(jnp.float32) * s["hd"] ** -0.5
            mask = jnp.tril(jnp.ones((n_tok, n_tok), bool))[None]
            p = jax.nn.softmax(jnp.where(mask, sc, -1e30), -1)
            o = jnp.einsum("hqk,khd->qhd", p.astype(jnp.bfloat16), v)
            x = x + o.reshape(n_tok, -1) @ lp["wo"]
            h = norm(x, lp["ffn_norm"])
            g = jax.nn.silu((h @ lp["w1"]).astype(jnp.float32))
            return x + ((g * (h @ lp["w3"]).astype(jnp.float32))
                        .astype(jnp.bfloat16) @ lp["w2"]), None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        return (norm(x[pos][None], params["final_norm"])
                @ params["lm_head"]).astype(jnp.float32)[0]

    seq = np.zeros(512, np.int32)
    seq[:len(prompt)] = prompt
    out = []
    for i in range(n):
        tok = int(np.argmax(np.asarray(
            logits_at(seq, len(prompt) - 1 + i))))
        seq[len(prompt) + i] = tok
        out.append(tok)
    return out


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 77])
def test_int8_control_reads_wider_than_a_sound_program(seed):
    params = llama_dense.init_weights(CFG, seed)
    rng = np.random.default_rng(seed)
    sample = []
    for _ in range(2):
        prompt = rng.integers(0, CFG["vocab_size"], 150).tolist()
        sample.append({"prompt": prompt,
                       "tokens": greedy_bf16(params, prompt, 24)})
    gaps = check.served_gaps(llama_dense, CFG, params, sample,
                             seq_len=512, n_read=32, control=True)
    assert gaps["tokens"] == 48
    served = gaps["served_sum"] / gaps["tokens"]
    control = gaps["control_sum"] / gaps["tokens"]
    assert control >= 3 * served and control > 0, (served, control)
    assert max(gaps["control"]) >= max(gaps["served"])
    limit = max(served, 1e-6) ** 0.5 * control ** 0.5
    compared = {"served_gap_mean": {"value": served, "limit": limit}}
    assert check.verdict(compared) is True
    compared["served_gap_mean"]["value"] = control
    assert check.verdict(compared) is False


def test_reference_needs_whole_blocks():
    params = llama_dense.init_weights(CFG, 1)
    with pytest.raises(ValueError):
        llama_dense.forward_logits(CFG, params, np.zeros(100, np.int32), [1])


def test_a_missing_reading_is_not_correct():
    assert check.verdict({"x": {"value": None, "limit": 1.0}}) is False
    assert check.verdict({"x": {"value": 0, "limit": 0}}) is True
    assert check.verdict({"x": {"value": 1, "limit": 0}}) is False
