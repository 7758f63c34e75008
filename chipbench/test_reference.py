"""The shared parts of the plain reference: tokenizer, routing rule,
compression faults, the check's sample and the attention block. The
dense squared-ReLU model's weights and forward pass are tested in
``test_dense_relu2.py``."""
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R

SEED = 2 ** 33 + 3


def test_window_attention_sees_only_the_last_keys():
    # one head, one KV head: with ``window`` w, row r attends keys
    # r-w+1 .. r; without, 0 .. r; never a key at or past n_real
    t, hd, n_real, w = 2 * R.QUERY_BLOCK, 8, 300, 5
    rng = np.random.default_rng(0)
    eye = np.eye(hd, dtype=np.float32)
    h = jnp.asarray(rng.normal(size=(t, hd)), jnp.float32)
    wts = {k: jnp.asarray(eye) for k in ("wq", "wk", "wv", "wo")}
    cfg = {"num_attention_heads": 1, "num_key_value_heads": 1,
           "head_dim": hd, "rope_theta": 10000.0}
    pos = jnp.arange(t)
    q = R.rope(h.reshape(t, 1, hd), pos, 10000.0)[:, 0]
    k = q
    s = np.asarray(q @ k.T) / np.sqrt(hd)
    for window in (None, w):
        got = np.asarray(R.gqa_attention(h, wts, cfg, n_real, False,
                                         window=window))
        for r in (0, 3, 200, 299):
            lo = 0 if window is None else max(0, r - window + 1)
            keys = np.arange(lo, min(r, n_real - 1) + 1)
            p = np.exp(s[r, keys] - s[r, keys].max())
            want = (p / p.sum()) @ np.asarray(h)[keys]
            np.testing.assert_allclose(got[r], want, rtol=1e-5, atol=1e-5)


def test_served_sequence_repeats_the_last_prompt_token():
    toks, first = R.served_sequence([5, 6, 7], [8, 9, 10])
    assert toks == [5, 6, 7, 7, 8, 9] and first == 3


def text(tokens, sentence_bytes=40):
    """ASCII sentences, exactly 4 * tokens bytes."""
    n = 4 * tokens
    s = " ".join(f"Item {i} " + "w" * (sentence_bytes - 9) + "."
                 for i in range(n // sentence_bytes + 2))
    return s[: n - 1] + "."


def test_routing_rule():
    b, g = 100, 2.0
    assert R.expected_pools(text(60), 20, "code", b, g, 4) == \
        {("short", False)}
    assert R.expected_pools(text(150), 10, "rag", b, g, 4) == \
        {("short", True)}
    assert R.expected_pools(text(150), 10, "code", b, g, 4) == \
        {("long", False)}
    assert R.expected_pools(text(250), 10, "prose", b, g, 4) == \
        {("long", False)}
    # within the margin of the edge either side stands
    assert R.expected_pools(text(97), 2, "code", b, g, 4) == \
        {("short", False), ("long", False)}
    # must-keep sentences (3 first, 2 last: 50 tokens here) over the
    # budget of 100 - 60: compression fails, the request goes long
    assert R.expected_pools(text(120), 60, "rag", b, g, 4) == \
        {("long", False)}


def test_compression_faults():
    sents = [f"Item {i} alpha beta gamma delta." for i in range(10)]
    prompt = " ".join(sents)
    kept = " ".join(sents[:3] + sents[5:6] + sents[8:])
    ids = R.tokenize(kept, 1024)
    assert R.compression_faults(prompt, kept, ids, 200, 1024) == []
    assert "first three or last two sentences dropped" in \
        R.compression_faults(prompt, " ".join(sents[1:4] + sents[8:]),
                             R.tokenize(" ".join(sents[1:4] + sents[8:]),
                                        1024), 200, 1024)
    assert "served tokens are not the compressed text" in \
        R.compression_faults(prompt, kept, ids[:-1], 200, 1024)
    assert "compressed prompt over its budget" in \
        R.compression_faults(prompt, kept, ids, 10, 1024)


def test_sample_takes_the_longest_each_pool_and_a_compressed_one():
    done = {i: {"prompt_ids": [0] * (10 + i), "served": [1] * 5,
                "pool": "short", "compressed": i == 3} for i in range(20)}
    done[50] = {"prompt_ids": [0] * 5, "served": [1] * 5, "pool": "long",
                "compressed": False}
    pick = R.sample_requests(done, SEED, 40, 6)
    assert pick[0] == 19 and 50 in pick and 3 in pick
    assert R.sample_requests(done, SEED, 40, 6) == pick


def done_mix(n, short_len):
    """Finished requests: every third of mixed length, the rest of
    ``short_len`` served tokens; one long-pool request in 17 and one
    compressed."""
    done = {}
    for i in range(n):
        served = short_len if i % 3 else 8 + (i * 37) % 90
        done[1000 + i] = {"prompt_ids": [1] * (20 + (i * 53) % 400),
                          "served": [2] * served,
                          "pool": "long" if i % 17 == 5 else "short",
                          "compressed": i == 11}
    return done


def test_sample_that_reaches_its_tokens_in_32_is_unchanged():
    # the pick of the 32-request rule on this seed, which covers 621
    # tokens with 15 requests; the 64-request rule must give the same
    done = done_mix(80, 30)
    pinned = [1075, 1022, 1011, 1062, 1038, 1024, 1019, 1002, 1065, 1049,
              1043, 1012, 1050, 1007, 1014]
    assert R.sample_requests(done, 4515000003, 600, 32) == pinned
    assert R.sample_requests(done, 4515000003, 600, 64) == pinned


def test_sample_of_short_requests_draws_past_32_to_its_tokens():
    # mostly 8-token requests, as azure's: 32 draws cover under 600
    # tokens, 64 do
    done = done_mix(90, 8)
    for i, k in enumerate(done):
        done[k]["served"] = [2] * (30 if i % 7 == 0 else 8)
    def tokens(pick):
        return sum(len(done[k]["served"]) for k in pick)
    old = R.sample_requests(done, 4515000003, 600, 32)
    new = R.sample_requests(done, 4515000003, 600, 64)
    assert len(old) == 32 and tokens(old) < 600
    assert new[:32] == old and 32 < len(new) <= 64
    assert tokens(new) >= 600 and tokens(new[:-1]) < 600
    # never more than there are
    few = {k: done[k] for k in list(done)[:40]}
    assert sorted(R.sample_requests(few, 1, 10 ** 6, 64)) == sorted(few)
