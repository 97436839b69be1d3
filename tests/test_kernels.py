"""Pallas kernels vs pure-jnp oracles (interpret mode), shape sweeps."""
import numpy as np
import jax.numpy as jnp
import jax.random as jr

from prop import grid


@grid(n=[40, 100], deg=[2, 5], f=[8, 24], bn=[4, 8], eb=[8, 16])
def test_spmm_sweep(n, deg, f, bn, eb):
    from repro.graph import csr, generators
    from repro.kernels.spmv_ell import ops
    g = generators.barabasi_albert(n, deg, seed=n + deg, directed=False)
    w = csr.normalized_pull_weights(g, 0.7746)
    x = np.random.default_rng(0).normal(size=(g.n, f)).astype(np.float32)
    out_k = ops.spmm(x, g, w, bn=bn, eb=eb)
    out_r = ops.spmm_reference(x, g, w)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)


def test_spmm_empty_rows():
    from repro.graph import csr
    from repro.kernels.spmv_ell import ops
    import numpy as np
    g = csr.from_edges(6, np.array([0, 1]), np.array([2, 2]))
    w = np.ones(g.m, np.float32)
    x = np.eye(6, 4, dtype=np.float32)
    out = np.asarray(ops.spmm(x, g, w, bn=2, eb=8))
    assert out[2, 0] == 1.0 and out[2, 1] == 1.0
    assert np.all(out[[0, 1, 3, 4, 5]] == 0)


@grid(bq=[4, 8], k_width=[16, 64])
def test_hp_join_sweep(bq, k_width, small_graph=None):
    from repro.graph import generators
    from repro.core import build
    from repro.kernels.hp_join import ops as hops
    g = generators.barabasi_albert(120, 3, seed=2, directed=False)
    idx = build.build_index(g, eps=0.15, exact_d=True)
    rng = np.random.default_rng(bq + k_width)
    us = rng.integers(0, g.n, 24).astype(np.int32)
    vs = rng.integers(0, g.n, 24).astype(np.int32)
    out_k = hops.query_pairs_kernel(idx, us, vs, bq=bq)
    out_r = hops.query_pairs_reference(idx, us, vs)
    np.testing.assert_allclose(out_k, out_r, atol=1e-6)
    host = np.array([idx.query_pair_host(int(u), int(v))
                     for u, v in zip(us, vs)])
    np.testing.assert_allclose(out_k, host, atol=1e-5)


@grid(b=[16, 64], m=[4, 8], d=[4, 8], layers=[1, 3])
def test_cin_sweep(b, m, d, layers):
    from repro.kernels.cin import ops as cops
    key = jr.PRNGKey(b * m + d)
    x0 = jr.normal(key, (b, m, d))
    hs = [m] + [6] * layers
    Ws = [jr.normal(jr.PRNGKey(i), (hs[i + 1], hs[i], m)) * 0.2
          for i in range(layers)]
    out_k = cops.cin_forward(x0, Ws, bb=min(16, b))
    out_r = cops.cin_forward_reference(x0, Ws)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_vs_dense_sweep():
    import jax
    from repro.models.flash_attention import flash_attention

    def dense_ref(q, k, v, window, isg):
        B, S, H, dh = q.shape
        scores = jnp.einsum("bshk,bthk->bhst", q, k) / np.sqrt(dh)
        pos = jnp.arange(S)
        m = pos[None, :] <= pos[:, None]
        if window > 0:
            local = pos[None, :] > pos[:, None] - window
            m = m & (jnp.bool_(isg > 0) | local)
        scores = jnp.where(m[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, -1)
        return jnp.einsum("bhst,bthk->bshk", w, v)

    for (S, chunk, window, isg) in [(32, 8, 0, 1.0), (64, 16, 12, 0.0),
                                    (32, 32, 4, 1.0), (48, 16, 0, 1.0)]:
        q = jr.normal(jr.PRNGKey(1), (2, S, 3, 8))
        k = jr.normal(jr.PRNGKey(2), (2, S, 3, 8))
        v = jr.normal(jr.PRNGKey(3), (2, S, 3, 8))
        o1 = flash_attention(q, k, v, jnp.float32(isg), window, chunk)
        o2 = dense_ref(q, k, v, window, isg)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5)
        g1 = jax.grad(lambda q: flash_attention(
            q, k, v, jnp.float32(isg), window, chunk).sum())(q)
        g2 = jax.grad(lambda q: dense_ref(q, k, v, window, isg).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-5)


def test_pallas_entry_points_have_no_interpret_default():
    """Interpret mode is decided by repro.kernels.interpret_mode() at
    the call site; an entry point that defaulted to interpret=True
    would silently run the interpreter on the chip."""
    import inspect

    from repro.core import shard_query, single_source, topk
    from repro.kernels.cin import cin, ops as cin_ops
    from repro.kernels.horner_push import horner_push, ops as hp_ops
    from repro.kernels.hp_join import hp_join, ops as join_ops
    from repro.kernels.spmv_ell import ops as ell_ops, spmv_ell

    modules = (cin, cin_ops, horner_push, hp_ops, hp_join, join_ops,
               spmv_ell, ell_ops, single_source, topk, shard_query)
    seen = 0
    for mod in modules:
        for name, fn in vars(mod).items():
            if not callable(fn) or name.startswith("__"):
                continue
            try:
                params = inspect.signature(fn).parameters
            except (TypeError, ValueError):
                continue
            if "interpret" in params:
                seen += 1
                assert params["interpret"].default is \
                    inspect.Parameter.empty, f"{mod.__name__}.{name}"
    assert seen >= 8
