"""The Llama family's forward_logits (benchmarks/families/llama.py) against
a hand-written two-layer case in float64 numpy with explicit loops (no
einsum, no vectorised attention); and check.compare."""
import math

import numpy as np

from benchmarks.harness import check
from benchmarks.harness.family import load, seed_key

llama = load({"name": "test", "family": "llama"})

M = {
    "hidden_size": 8, "intermediate_size": 12, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
    "vocab_size": 11, "rope_theta": 100.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "attention_bias": True,
}


def by_hand(w, m, tokens):
    E, Hq, Hkv, D = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    T = len(tokens)

    def norm(x, g):
        return x / math.sqrt(float(np.mean(x * x)) + m["rms_norm_eps"]) * g

    def rope(vec, pos):
        out = vec.copy()
        for i in range(D // 2):
            ang = pos / (m["rope_theta"] ** (2 * i / D))
            a, b = vec[i], vec[i + D // 2]
            out[i] = a * math.cos(ang) - b * math.sin(ang)
            out[i + D // 2] = b * math.cos(ang) + a * math.sin(ang)
        return out

    xs = [w["embed"][t].astype(np.float64) for t in tokens]
    for l in range(m["num_hidden_layers"]):
        lp = {k: v[l].astype(np.float64) for k, v in w["layers"].items()}
        qs, ks, vs = [], [], []
        for p, x in enumerate(xs):
            h = norm(x, lp["attn_norm"])
            q = (h @ lp["wq"] + lp["bq"]).reshape(Hq, D)
            k = (h @ lp["wk"] + lp["bk"]).reshape(Hkv, D)
            v = (h @ lp["wv"] + lp["bv"]).reshape(Hkv, D)
            qs.append([rope(q[i], p) for i in range(Hq)])
            ks.append([rope(k[i], p) for i in range(Hkv)])
            vs.append(v)
        new = []
        for p, x in enumerate(xs):
            heads = []
            for hq in range(Hq):
                kv = hq // (Hq // Hkv)
                sc = np.array([qs[p][hq] @ ks[j][kv] / math.sqrt(D) for j in range(p + 1)])
                pr = np.exp(sc - sc.max())
                pr /= pr.sum()
                heads.append(sum(pr[j] * vs[j][kv] for j in range(p + 1)))
            x = x + np.concatenate(heads) @ lp["wo"]
            h = norm(x, lp["mlp_norm"])
            g = h @ lp["w_gate"]
            x = x + ((g / (1 + np.exp(-g))) * (h @ lp["w_up"])) @ lp["w_down"]
            new.append(x)
        xs = new
    fn = w["final_norm"].astype(np.float64)
    return np.stack([norm(x, fn) @ w["embed"].astype(np.float64).T for x in xs])


def test_forward_matches_the_hand_written_case():
    import jax
    import jax.numpy as jnp

    w = jax.jit(lambda k: llama.make_weights(M, k, jnp.float32))(seed_key(2**31 + 9))
    wn = jax.tree.map(np.asarray, w)
    assert wn["layers"]["bq"].std() > 0.01 and abs(wn["layers"]["attn_norm"].mean() - 1) < 0.2
    tokens = np.array([3, 7, 1, 10, 4, 0, 0, 0], np.int32)  # 5 real + padding
    idx = np.arange(5, dtype=np.int32)
    got = np.asarray(llama.forward_logits(w, M, jnp.asarray(tokens), jnp.asarray(idx)))
    want = by_hand(wn, M, tokens[:5])
    assert got.shape == (5, 11)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_weights_are_a_function_of_the_seed_and_untied_heads_exist():
    import jax
    import jax.numpy as jnp

    m = dict(M, tie_word_embeddings=False, attention_bias=False)
    a = llama.make_weights(m, seed_key(5), jnp.bfloat16)
    b = llama.make_weights(m, seed_key(5), jnp.bfloat16)
    c = llama.make_weights(m, seed_key(6), jnp.bfloat16)
    assert "lm_head" in a and "bq" not in a["layers"]
    assert a["layers"]["wq"].dtype == jnp.bfloat16 and a["final_norm"].dtype == jnp.float32
    assert bool((a["embed"] == b["embed"]).all()) and not bool((a["embed"] == c["embed"]).all())


def test_compare_separates_sound_from_unsound():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(16, 50)).astype(np.float32)
    ids = logits.argmax(-1)
    lp = logits - np.logaddexp.reduce(logits.astype(np.float64), axis=-1, keepdims=True)
    exact = [float(lp[i, t]) for i, t in enumerate(ids)]
    ok = check.compare([{"served_ids": ids, "served_logprobs": exact}], [logits], llama.LIMITS)
    assert ok["ok"] and ok["logprob_mse"] < 1e-12 and ok["argmax_exact"] == 16
    off = [x + 0.2 for x in exact]
    assert not check.compare([{"served_ids": ids, "served_logprobs": off}], [logits], llama.LIMITS)["ok"]
    wrong = ids.copy()
    wrong[0] = int(logits[0].argmin())
    bad = check.compare(
        [{"served_ids": wrong, "served_logprobs": [float(lp[i, t]) for i, t in enumerate(wrong)]}],
        [logits], llama.LIMITS)
    assert not bad["ok"] and bad["deficit_max"] > 1.0
    assert not check.compare([{"served_ids": ids[:3], "served_logprobs": exact}], [logits], llama.LIMITS)["ok"]
