"""kNN-LM serving on RWKV-6 Finch at a tiny size with seeded float32
weights, against the plain float32 reference (models/rwkv6_ref.py): the
block (chunked prefill, step-by-step decode through RWKVState, the two
joined); slot prefill of right-padded rows from a carried state for every
recurrent and attention family; the served step's outputs; the Server
admitting two requests in turn into one slot."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import get_config, scaled_down
from repro.core import binary, quantize, retrieval
from repro.dist import steps
from repro.models import lm, rwkv6_ref
from repro.runtime import server as server_mod

TOL = 2e-5


def _finch(**kw):
    cfg = dataclasses.replace(scaled_down(get_config("rwkv6-1.6b"), **kw),
                              dtype="float32")
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def _tokens(cfg, n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                              cfg.vocab_size)


@pytest.mark.parametrize("mode", ["prefill", "decode", "prefill_then_decode"])
def test_finch_block_matches_reference(mode):
    cfg, params = _finch()
    toks = _tokens(cfg, 45)
    ref_h, ref_logits = rwkv6_ref.forward(params, cfg, toks)
    first = 0                           # the first position compared
    with jax.default_matmul_precision("highest"):
        if mode == "prefill":          # chunked WKV over 45 tokens
            logits, _, h = lm.forward(params, cfg, toks[None],
                                      return_hidden=True)
            h, logits = h[0], logits[0]
        else:                          # RWKVState: from zero, or a prefill
            cut = 0 if mode == "decode" else 30
            state = lm.init_decode_state(cfg, 1, 64)
            hs, ls = [], []
            if cut:
                h0, l0, rows = lm.prefill_last(params, cfg, toks[None, :cut],
                                               jnp.asarray([cut]))
                state = lm.write_rows(cfg, state, rows, jnp.asarray([0]))
                hs, ls, first = [h0], [l0], cut - 1
            for t in range(cut, 45):
                lg, state, ht = lm.decode_step(params, cfg, toks[None, t:t + 1],
                                               state, return_hidden=True)
                hs.append(ht[0])
                ls.append(lg[0])
            h, logits = jnp.concatenate(hs), jnp.concatenate(ls)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref_h[first:]),
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_logits[first:]), atol=TOL)


def test_decode_probe_is_the_last_block():
    """The decode step's probe holds the last block's input state and
    input, and its output and new WKV state follow from them through the
    reference block; r, k, v, log-decay and y are the recurrence's."""
    cfg, params = _finch()
    toks = _tokens(cfg, 12)
    state = lm.init_decode_state(cfg, 2, 32)
    with jax.default_matmul_precision("highest"):
        for t in range(11):
            _, state = lm.decode_step(params, cfg,
                                      jnp.stack([toks[t:t + 1]] * 2), state)
        _, new, _, probe = lm.decode_step(
            params, cfg, jnp.stack([toks[11:12]] * 2), state,
            return_hidden=True, return_probe=True)
    last = jax.tree_util.tree_map(lambda a: a[-1], params["blocks"])
    st = jax.tree_util.tree_map(lambda a: a[-1], state["cache"])
    for n in ("wkv", "shift_t", "shift_c"):
        np.testing.assert_array_equal(probe[n], getattr(st, n))
    x, (wkv, _, _) = rwkv6_ref._layer(last, cfg, probe["x_in"][0],
                                      (st.wkv[0], st.shift_t[0], st.shift_c[0]))
    np.testing.assert_allclose(probe["x_out"][0], x, atol=TOL)
    np.testing.assert_allclose(new["cache"].wkv[-1, 0], wkv, atol=TOL)
    H, hd = wkv.shape[:2]
    r, k, v, logw, y = (probe[n][0].reshape(H, hd)
                        for n in ("r", "k", "v", "logw", "y"))
    kv = k[:, :, None] * v[:, None, :]
    np.testing.assert_allclose(np.exp(logw)[..., None] * st.wkv[0] + kv, wkv,
                               atol=TOL)
    np.testing.assert_allclose(
        y, jnp.einsum("hi,hij->hj", r, st.wkv[0] + last["att"]["bonus"][
            ..., None] * kv, precision="highest"), atol=TOL)
    with pytest.raises(ValueError, match="probe"):
        gcfg = dataclasses.replace(scaled_down(get_config("gemma-2b")),
                                   dtype="float32")
        lm.decode_step(lm.init_params(jax.random.PRNGKey(0), gcfg), gcfg,
                       toks[None, :1] % gcfg.vocab_size,
                       lm.init_decode_state(gcfg, 1, 8), return_probe=True)


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-2.7b", "gemma-2b"])
def test_slot_prefill_padded_and_carried(arch):
    """Right-padded rows of one prefill call give each row's own last
    hidden state and state; a prompt prefilled in two parts (the second
    from the first's state, ``read_rows``) and then decoded equals one
    forward over the whole sequence."""
    cfg = dataclasses.replace(scaled_down(get_config(arch)), dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    a, b = _tokens(cfg, 13, 2), _tokens(cfg, 7, 3)
    full = jnp.concatenate([a, b])
    with jax.default_matmul_precision("highest"):
        ref_logits, _ = lm.forward(params, cfg, full[None])
        toks = jnp.zeros((2, 16), jnp.int32).at[0, :13].set(a).at[1, :7].set(b)
        h, logits, rows = lm.prefill_last(params, cfg, toks,
                                          jnp.asarray([13, 7]))
        alone, _, _ = lm.prefill_last(params, cfg, b[None], jnp.asarray([7]))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(ref_logits[0, 12]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(h[1]), np.asarray(alone[0]),
                                   atol=1e-4)
        state = lm.init_decode_state(cfg, 3, 32)
        state = lm.write_rows(cfg, state, rows, jnp.asarray([2, 3]))  # 3 drops
        assert list(np.asarray(state["pos"])) == [0, 0, 13]
        if cfg.attention_free:          # a purely recurrent state carries
            _, lg2, rows2 = lm.prefill_last(
                params, cfg, b[None], jnp.asarray([7]),
                lm.read_rows(cfg, state, jnp.asarray([2])))
            np.testing.assert_allclose(np.asarray(lg2[0]),
                                       np.asarray(ref_logits[0, 19]),
                                       atol=1e-4)
        zeroed = lm.zero_rows(cfg, state, jnp.asarray([2]))
        for leaf in jax.tree_util.tree_leaves(lm.read_rows(
                cfg, zeroed, jnp.asarray([2]))):
            assert not np.asarray(leaf).any()


def _store(cfg, n=512, seed=4):
    """A datastore keyed by ITQ codes of the model's own hidden states."""
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(seed), (8, 64), 0,
                              cfg.vocab_size)
    _, _, h = lm.forward(params, cfg, toks, return_hidden=True)
    h = h.reshape(-1, cfg.d_model)[:n]
    vals = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), 0,
                              cfg.vocab_size)
    return retrieval.build_datastore(h, vals, cfg.retrieval.code_bits,
                                     itq_iters=5)


def _retrieval_cfg():
    cfg, params = _finch()
    cfg = dataclasses.replace(cfg, retrieval=dataclasses.replace(
        cfg.retrieval, code_bits=64, k=8, datastore_size=512))
    return cfg, params


@pytest.mark.parametrize("step", ["decode", "admit"])
def test_served_step_outputs_are_consistent(step):
    """The key, codes, (dists, ids) and LM logits a served step returns
    are what its log-probs were built from."""
    cfg, params = _retrieval_cfg()
    store = _store(cfg)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    state = lm.init_decode_state(cfg, 2, 32)
    with mesh:
        if step == "decode":
            fn, _, _ = steps.make_serve_step(cfg, mesh, 32)
            logp, _, outs = fn(params, jnp.asarray([[3], [5]]), state,
                               jnp.asarray([True, True]), store)
            logp = logp[:, 0]
        else:
            fn = steps.make_admit_step(cfg, mesh, 32)
            toks = jnp.zeros((2, 8), jnp.int32).at[0].set(
                _tokens(cfg, 8)).at[1, :5].set(_tokens(cfg, 5, 2))
            logp, new_state, outs = fn(params, toks, jnp.asarray([8, 5]),
                                       jnp.asarray([1, 2]), state, store)
            assert list(np.asarray(new_state["pos"])) == [0, 8]
    codes = binary.pack_bits(quantize.itq_encode(outs["key"], store.itq))
    assert (np.asarray(codes) == np.asarray(outs["codes"])).all()
    d, i = rwkv6_ref.knn(store.codes, outs["codes"], cfg.retrieval.k)
    assert (np.asarray(outs["dists"]) == np.asarray(d)).all()
    n = store.codes.shape[0]
    got = rwkv6_ref.knn_log_probs(outs["dists"], outs["ids"], store.values,
                                  cfg.vocab_size)
    want = rwkv6_ref.interpolate(outs["lm_logits"], got,
                                 cfg.retrieval.interpolation)
    assert (np.asarray(outs["ids"]) < n).all()
    np.testing.assert_allclose(np.asarray(logp), np.asarray(want), atol=1e-5)


def test_server_slot_prefill_two_requests_one_slot(monkeypatch):
    """One slot, two requests in turn: each is served its first token from
    its admission, no prompt token reaches the decode step, and the second
    (in the reused slot) matches the reference from a zero state."""
    cfg, params = _retrieval_cfg()
    store = _store(cfg)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    srv = server_mod.Server(cfg, mesh, params, max_batch=1, max_len=32,
                            store=store)
    fed = []
    step = server_mod.Server._step

    def spy(self, token, active, r):
        fed.append(int(token[0, 0]))
        return step(self, token, active, r)

    monkeypatch.setattr(server_mod.Server, "_step", spy)
    rng = np.random.default_rng(0)
    reqs = [server_mod.Request(uid=u, prompt=rng.integers(
        0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=4)
        for u, n in ((0, 9), (1, 6))]
    for r in reqs:
        srv.submit(r)
    srv.run(max_ticks=20)
    assert [r.status for r in reqs] == ["done", "done"]
    assert srv.counters["first_tokens_served"] == 2
    assert srv.counters["prefills"] == 2
    assert srv.counters["slot_state_resets"] == 2
    assert srv.counters["prefill_tokens"] == 15
    # the decode step sees only served tokens, never a prompt token
    assert fed == reqs[0].out_tokens[:-1] + reqs[1].out_tokens[:-1]
    # the second request against the reference from a zero state
    r = reqs[1]
    seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
    h, logits = rwkv6_ref.forward(params, cfg, seq)
    p = len(r.prompt)
    q = rwkv6_ref.encode(h[p - 1:], store.itq)
    d, i = rwkv6_ref.knn(store.codes, q, cfg.retrieval.k)
    mixed = rwkv6_ref.interpolate(
        logits[p - 1:], rwkv6_ref.knn_log_probs(d, i, store.values,
                                                cfg.vocab_size),
        cfg.retrieval.interpolation)
    assert list(np.asarray(jnp.argmax(mixed, axis=-1))) == r.out_tokens
