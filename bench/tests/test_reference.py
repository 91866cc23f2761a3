"""The plain reference against the program's own pure-JAX path
(``core/cws.py``, ``core/regen.py``, ``core/hashing.py``,
``core/linear_model.py``) at a tiny size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference

D, K, B_I = 24, 32, 4


def rows(seed=3, n=40):
    cfg = {"dim": D, "n_classes": 3, "nnz_share": 0.3, "spike_prob": 0.05}
    x, y = gen.rows_for(cfg, seed, n)
    return x.at[5].set(0.0), y          # one all-zero row


@pytest.mark.parametrize("mode", ["stored", "regen"])
def test_params_and_codes_match_the_program(mode):
    from repro.core import cws, hashing, regen
    key = gen.sub_key(2 ** 40 + 1, gen.KEY_CWS)
    got = reference.cws_params({"params": mode, "dim": D,
                                "num_hashes": K}, key)
    if mode == "stored":
        p = cws.make_cws_params(key, D, K)
    else:
        p = regen.regen_params(key, D, K)
    for a, b in zip(got, (p.r, p.log_c, p.beta)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x, _ = rows()
    i_star, t_star = cws.cws_hash_reference(x, p)
    want = hashing.encode(i_star, t_star, b_i=B_I)
    want = np.where(np.asarray(want) < 0, 0, np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(reference.codes(x, *got, b_i=B_I)), want)


def test_bfloat16_codes_differ():
    x, _ = rows(n=64)
    p = reference.stored_params(gen.sub_key(7, gen.KEY_CWS), D, K)
    f32 = np.asarray(reference.codes(x, *p, b_i=B_I))
    bf16 = np.asarray(reference.codes(x, *p, b_i=B_I, dtype=jnp.bfloat16))
    assert np.mean(f32 != bf16) > 0


def test_unpack_inverts_the_programs_packing():
    from repro.core import hashing
    codes = np.random.default_rng(0).integers(0, 256, (5, 36)).astype(np.int32)
    words = hashing.pack_codes(jnp.asarray(codes), b=8)
    np.testing.assert_array_equal(reference.unpack(words, 36, 8), codes)


def test_indices_logits_and_steps_match_the_program():
    from repro import optim
    from repro.core import linear_model as lm
    x, y = rows(n=48)
    p = reference.stored_params(gen.sub_key(9, gen.KEY_CWS), D, K)
    idx = reference.indices(reference.codes(x, *p, b_i=B_I), B_I)
    kw, kb = jax.random.split(jax.random.PRNGKey(1))
    w = 0.1 * jax.random.normal(kw, (K << B_I, 3))
    b = 0.1 * jax.random.normal(kb, (3,))
    np.testing.assert_allclose(
        np.asarray(reference.logits(w, b, idx)),
        np.asarray(lm.bag_logits(lm.LinearParams(w, b), idx)),
        rtol=1e-5, atol=1e-6)

    cfg = lm.TrainCfg(n_classes=3, steps=5, lr=0.05, l2=1e-5,
                      batch_size=16)
    tx = lm.make_linear_tx(cfg)
    params = lm.LinearParams(jnp.zeros_like(w), jnp.zeros_like(b))
    state = tx.init(params)
    batches = [(idx[i * 16:(i + 1) * 16], y[i * 16:(i + 1) * 16])
               for i in range(3)]
    for i, (bi, by) in enumerate(batches):
        g = jax.grad(lm._loss_fn)(params, bi, by, cfg, lm.bag_logits)
        upd, state = tx.update(g, state, params, jnp.int32(i))
        params = optim.apply_updates(params, upd)
    got = reference.train(
        (jnp.zeros_like(w), jnp.zeros_like(b)), batches, lr=0.05,
        total_steps=5, n_classes=3, l2=1e-5, early=2)
    last = got["last"]
    assert set(got) == {"last", "early", "grad1"}
    # gradient entries that cancel to round-off take Adam steps of any
    # size, so entries may differ where the sums ran in another order;
    # the norms the check compares agree
    for a, b in ((last[0], params.w), (last[1], params.b)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.mean(np.isclose(a, b, rtol=1e-4, atol=1e-6)) > 0.98
        assert np.linalg.norm(a) == pytest.approx(np.linalg.norm(b),
                                                  rel=1e-2)
