"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles,
interpret mode (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyp_compat import hypothesis, st
from repro.kernels.etf_ft import kernel as etfk, ops as etfo, ref as etfr
from repro.kernels.flash_attention import kernel as fak, ref as far
from repro.kernels.rg_lru import kernel as rgk, ref as rgr
from repro.kernels.ssd_scan import kernel as ssdk, ref as ssdr


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # B, S, H, K, D, window, softcap, dtype
    (1, 256, 4, 4, 64, 0, 0.0, "float32"),     # MHA
    (2, 256, 8, 2, 64, 0, 0.0, "float32"),     # GQA
    (1, 256, 4, 1, 128, 0, 0.0, "float32"),    # MQA, d128
    (1, 512, 4, 2, 64, 128, 0.0, "float32"),   # sliding window
    (1, 256, 4, 4, 64, 0, 30.0, "float32"),    # softcap
    (2, 256, 8, 2, 64, 0, 0.0, "bfloat16"),    # bf16
    (1, 384, 6, 3, 32, 0, 0.0, "float32"),     # non-128 block tail (S=384)
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_oracle(case):
    B, S, H, K, D, W, cap, dt = case
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), dt)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, D), dt)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, D), dt)
    out = fak.flash_attention_fwd(q, k, v, causal=True, window=W,
                                  softcap=cap, block_q=128, block_k=128,
                                  interpret=True)
    expect = far.mha_reference(q, k, v, causal=True, window=W, softcap=cap)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - expect.astype(jnp.float32))))
    tol = 2e-2 if dt == "bfloat16" else 1e-4
    assert err < tol, (case, err)


def test_flash_block_shape_sweep():
    B, S, H, K, D = 1, 256, 2, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, D))
    expect = far.mha_reference(q, k, v)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = fak.flash_attention_fwd(q, k, v, block_q=bq, block_k=bk,
                                      interpret=True)
        assert float(jnp.max(jnp.abs(out - expect))) < 1e-4, (bq, bk)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [
    (1, 32, 2, 8, 4, 16), (2, 64, 3, 16, 8, 16), (1, 128, 2, 16, 16, 32),
])
def test_ssd_vs_sequential_oracle(shape):
    B, S, H, P, N, Q = shape
    ks = [jax.random.PRNGKey(i) for i in range(5)]
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bh = jax.random.normal(ks[3], (B, S, H, N)) * 0.5
    Ch = jax.random.normal(ks[4], (B, S, H, N)) * 0.5
    y, h = ssdk.ssd_fwd(x, dt, A, Bh, Ch, chunk=Q, interpret=True)
    y2, h2 = ssdr.ssd_reference(x, dt, A, Bh, Ch)
    assert float(jnp.max(jnp.abs(y - y2))) < 1e-4
    assert float(jnp.max(jnp.abs(h - h2))) < 1e-4


def test_ssd_bf16_tolerance():
    B, S, H, P, N, Q = 1, 64, 2, 16, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, P),
                          jnp.bfloat16) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)))
    Bh = (jax.random.normal(jax.random.PRNGKey(3), (B, S, H, N)) * 0.5)
    Ch = (jax.random.normal(jax.random.PRNGKey(4), (B, S, H, N)) * 0.5)
    y, _ = ssdk.ssd_fwd(x, dt, A, Bh, Ch, chunk=Q, interpret=True)
    y2, _ = ssdr.ssd_reference(x.astype(jnp.float32), dt, A, Bh, Ch)
    rel = float(jnp.max(jnp.abs(y.astype(jnp.float32) - y2))) / (
        float(jnp.max(jnp.abs(y2))) + 1e-9)
    assert rel < 3e-2


# ---------------------------------------------------------------------------
# rg-lru scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1, 32, 128), (2, 64, 256), (1, 96, 384)])
def test_rg_lru_vs_oracle(shape):
    B, S, C = shape
    a = jax.random.uniform(jax.random.PRNGKey(0), (B, S, C),
                           minval=0.6, maxval=0.999)
    b = jax.random.normal(jax.random.PRNGKey(1), (B, S, C)) * 0.1
    out = rgk.rg_lru_fwd(a, b, chunk=16, block_c=128, interpret=True)
    expect = rgr.rg_lru_reference(a, b)
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-5


# ---------------------------------------------------------------------------
# etf finish-time search
# ---------------------------------------------------------------------------
@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(seed=st.integers(0, 1000), b=st.integers(1, 8),
                  r=st.integers(2, 32))
def test_etf_kernel_property(seed, b, r):
    P = 19
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    avail = jax.random.uniform(ks[0], (b, r, P)) * 10
    free = jax.random.uniform(ks[1], (b, P)) * 10
    ex = jnp.where(jax.random.uniform(ks[2], (b, r, P)) < 0.3, jnp.inf,
                   jax.random.uniform(ks[3], (b, r, P)) * 5)
    now = jnp.zeros((b,))
    ft1, s1, p1 = etfk.etf_ft_search(avail, free, ex, now, interpret=True)
    ft2, s2, p2 = etfr.etf_ft_reference(avail, free, ex, now)
    np.testing.assert_allclose(np.asarray(ft1), np.asarray(ft2), rtol=1e-6)
    assert (np.asarray(s1) == np.asarray(s2)).all()
    assert (np.asarray(p1) == np.asarray(p2)).all()


def test_etf_kernel_min_is_achievable():
    """The returned (slot, pe) must actually achieve the returned FT."""
    b, r, P = 3, 8, 19
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    avail = jax.random.uniform(ks[0], (b, r, P)) * 10
    free = jax.random.uniform(ks[1], (b, P)) * 10
    ex = jax.random.uniform(ks[2], (b, r, P)) * 5
    now = jnp.zeros((b,))
    ft, s, p = etfk.etf_ft_search(avail, free, ex, now, interpret=True)
    for i in range(b):
        si, pi = int(s[i]), int(p[i])
        direct = max(float(avail[i, si, pi]), float(free[i, pi]), 0.0) \
            + float(ex[i, si, pi])
        assert abs(direct - float(ft[i])) < 1e-5


# ---------------------------------------------------------------------------
# masked decision search + push rows (PR-10, the simulator hot path)
# ---------------------------------------------------------------------------
def _masked_case(seed, s, r, tie_frac=0.0):
    P = 19
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    avail = jax.random.uniform(ks[0], (s, r, P)) * 10
    free = jax.random.uniform(ks[1], (s, P)) * 10
    ex = jnp.where(jax.random.uniform(ks[2], (s, r, P)) < 0.3, jnp.inf,
                   jax.random.uniform(ks[3], (s, r, P)) * 5)
    if tie_frac:
        # quantize hard so many (slot, pe) pairs tie for the minimum —
        # the tie-break (first flat index) is the contract under test
        avail = jnp.round(avail / 5) * 5
        free = jnp.round(free / 5) * 5
        ex = jnp.round(ex)
    now = jax.random.uniform(ks[4], (s,)) * 3
    slot_ok = jax.random.uniform(ks[5], (s, r)) < 0.7
    alive = jax.random.uniform(ks[6], (s, P)) < 0.8
    return avail, free, ex, now, slot_ok, alive


def _masked_oracle(avail, free, ex, now, slot_ok, alive):
    """Inline numpy restatement of the simulator's masked argmin."""
    a, f, e = np.asarray(avail), np.asarray(free), np.asarray(ex)
    ft = np.maximum(np.maximum(a, f[:, None, :]),
                    np.asarray(now)[:, None, None]) + e
    ok = (np.asarray(slot_ok)[:, :, None] & np.asarray(alive)[:, None, :]
          & np.isfinite(ft))
    ft = np.where(ok, ft, etfk.BIG).astype(np.float32)
    S, R, P = ft.shape
    flat = ft.reshape(S, -1)
    idx = flat.argmin(1)
    mn = flat[np.arange(S), idx]
    return mn, idx // P, idx % P, mn < etfk.BIG


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(seed=st.integers(0, 1000), s=st.integers(1, 6),
                  r=st.integers(2, 24), ties=st.booleans())
def test_etf_masked_kernel_property(seed, s, r, ties):
    case = _masked_case(seed, s, r, tie_frac=1.0 if ties else 0.0)
    ft1, s1, p1, ok1 = etfk.etf_ft_search_masked(*case, interpret=True)
    ft2, s2, p2, ok2 = etfr.etf_ft_masked_reference(*case)
    ft3, s3, p3, ok3 = _masked_oracle(*case)
    for tag, (ft, sl, pe, ok) in (("kernel", (ft1, s1, p1, ok1)),
                                  ("xla", (ft2, s2, p2, ok2))):
        assert np.asarray(ft).tobytes() == ft3.tobytes(), tag
        assert (np.asarray(sl) == s3).all(), tag
        assert (np.asarray(pe) == p3).all(), tag
        assert (np.asarray(ok) == ok3).all(), tag


def test_etf_masked_all_masked_lane():
    """Everything masked -> slot 0 / pe 0, feasible False on both paths
    (the simulator relies on this to fall back to its own no-op)."""
    s, r, P = 2, 4, 19
    avail = jnp.ones((s, r, P))
    free = jnp.zeros((s, P))
    ex = jnp.ones((s, r, P))
    now = jnp.zeros((s,))
    slot_ok = jnp.zeros((s, r), bool)
    alive = jnp.ones((s, P), bool)
    for fn in (lambda: etfk.etf_ft_search_masked(
                   avail, free, ex, now, slot_ok, alive, interpret=True),
               lambda: etfr.etf_ft_masked_reference(
                   avail, free, ex, now, slot_ok, alive)):
        _, sl, pe, ok = fn()
        assert (np.asarray(sl) == 0).all() and (np.asarray(pe) == 0).all()
        assert not np.asarray(ok).any()


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(seed=st.integers(0, 1000), s=st.integers(1, 4),
                  k=st.integers(1, 8), mp=st.integers(1, 6))
def test_push_rows_kernel_vs_naive(seed, s, k, mp):
    P, C = 19, 6
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pfin = jax.random.uniform(ks[0], (s, k, mp)) * 100
    cost = jax.random.uniform(ks[1], (s, k, mp)) * 10
    pcl = jax.random.randint(ks[2], (s, k, mp), 0, C)
    pv = jax.random.uniform(ks[3], (s, k, mp)) < 0.6
    pecl = jnp.asarray(np.random.RandomState(seed).randint(0, C, P))
    bases = jax.random.uniform(ks[4], (s, k)) * 50
    # naive [S, K, MP, P] oracle — exactly the simulator's inline max
    cross = (np.asarray(pcl)[..., None]
             != np.asarray(pecl)[None, None, None, :])
    contrib = np.where(np.asarray(pv)[..., None],
                       np.asarray(pfin)[..., None]
                       + np.asarray(cost)[..., None] * cross.astype(
                           np.float32),
                       -np.inf)
    naive = np.maximum(contrib.max(axis=2),
                       np.asarray(bases)[..., None]).astype(np.float32)
    got_k = etfk.push_rows(pfin, cost, pcl, pv, pecl, bases,
                           interpret=True)
    got_r = etfr.push_rows_reference(pfin, cost, pcl, pv, pecl, bases, C)
    np.testing.assert_array_equal(np.asarray(got_r), naive)
    np.testing.assert_array_equal(np.asarray(got_k), naive)


def test_etf_ops_dispatch_counts(monkeypatch):
    """Each `ops` call tallies exactly one dispatch under its backend."""
    case = _masked_case(0, 1, 4)
    single = tuple(x[0] for x in case)
    before = dict(etfo.DISPATCH_COUNT)
    etfo.etf_decide(*single, mode="xla")
    etfo.etf_decide(*single, mode="pallas-interpret")
    assert etfo.DISPATCH_COUNT["etf_xla"] == before["etf_xla"] + 1
    assert etfo.DISPATCH_COUNT["etf_pallas_interpret"] == \
        before["etf_pallas_interpret"] + 1


def test_kernel_mode_resolution(monkeypatch):
    km = etfo.kernel_mode
    assert km("off") == "off" and km("0") == "off"
    assert km("xla") == "xla"
    assert km("pallas-interpret") == "pallas-interpret"
    on_tpu = jax.default_backend() == "tpu"
    assert km("auto") == ("pallas" if on_tpu else "xla")
    if on_tpu:
        assert km("pallas") == "pallas"
    else:
        # native Pallas off-TPU is an error, never a silent interpret run
        with pytest.raises(ValueError, match="pallas-interpret"):
            km("pallas")
    # idempotent on resolved modes
    for m in ("off", "xla", "pallas-interpret") + (("pallas",) if on_tpu
                                                  else ()):
        assert km(km(m)) == km(m)
    monkeypatch.setenv("REPRO_SIM_KERNELS", "off")
    assert km() == "off"
    with pytest.raises(ValueError, match="REPRO_SIM_KERNELS"):
        km("bogus")


def test_interpret_limit_derived_from_block_shape(monkeypatch):
    """The interpret-mode bailout must come from the kernel's block
    geometry (cells budget / per-step block), not a hard-coded batch:
    at the default [64, 19->128] geometry it reproduces the old B > 64."""
    assert etfo.interpret_batch_limit(64, 19) == 64
    # half the rows -> twice the batch; wider PE pad -> proportionally less
    assert etfo.interpret_batch_limit(32, 19) == 128
    assert etfo.interpret_batch_limit(64, 129) == 32
    monkeypatch.setenv("REPRO_ETF_FT_INTERPRET_CELLS", str(64 * 128 * 2))
    assert etfo.interpret_batch_limit(64, 19) == 2


def test_interpret_fallback_boundary_agrees(monkeypatch):
    """`etf_ft` just below the limit (kernel) and just above (jnp ref
    fallback) must agree — the silent-fallback bug was the two paths
    drifting unnoticed."""
    # shrink the budget so the boundary is tiny and cheap to straddle
    monkeypatch.setenv("REPRO_ETF_FT_INTERPRET_CELLS", str(8 * 128 * 2))
    r, P = 8, 19
    limit = etfo.interpret_batch_limit(r, P)
    assert limit == 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B = limit + 1
    avail = jax.random.uniform(ks[0], (B, r, P)) * 10
    free = jax.random.uniform(ks[1], (B, P)) * 10
    ex = jax.random.uniform(ks[2], (B, r, P)) * 5
    now = jnp.zeros((B,))
    before = etfo.DISPATCH_COUNT["etf_ft_ref_fallback"]
    # B = limit: kernel path (no fallback tally)
    out_k = etfo.etf_ft(avail[:limit], free[:limit], ex[:limit],
                        now[:limit], interpret=True)
    assert etfo.DISPATCH_COUNT["etf_ft_ref_fallback"] == before
    # B = limit + 1: reference fallback (tallied)
    out_r = etfo.etf_ft(avail, free, ex, now, interpret=True)
    assert etfo.DISPATCH_COUNT["etf_ft_ref_fallback"] == before + 1
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b)[:limit])
