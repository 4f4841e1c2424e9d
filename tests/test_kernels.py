"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)

ATTN_SHAPES = [
    (1, 128, 1, 32),
    (2, 256, 4, 64),
    (1, 512, 2, 128),
    (2, 384, 3, 64),    # seq not divisible by 256 -> block fallback
]


@pytest.mark.parametrize("B,S,H,hd", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_forward(B, S, H, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    bq = 128 if S % 128 == 0 else 64
    out = ops.flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=bq, block_k=bq)
    expect = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_grads_match_reference():
    B, S, H, hd = 1, 128, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))

    def f_kernel(q, k, v):
        return (ops.flash_attention(q, k, v, causal=True,
                                    interpret=True, block_q=64,
                                    block_k=64) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.attention_ref(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_kernel, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


WKV_SHAPES = [(1, 64, 1, 64), (2, 128, 3, 64), (1, 96, 2, 64)]


@pytest.mark.parametrize("B,T,H,N", WKV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_wkv_kernel(B, T, H, N, dtype):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, N), dtype) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, N), dtype) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, N), dtype) * 0.5
    w = jnp.exp(-jnp.exp(
        jax.random.normal(ks[3], (B, T, H, N)) * 0.5 - 2.0)).astype(dtype)
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, N, N)) * 0.1
    out, sT = ops.rwkv6_wkv(r, k, v, w, u, s0, chunk=32, interpret=True)
    expect, sT_ref = ref.wkv_ref(r, k, v, w, u, s0)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                               atol=tol * 10, rtol=tol * 10)


SSD_SHAPES = [(1, 64, 2, 64, 1, 64), (2, 128, 4, 32, 2, 16),
              (1, 96, 3, 16, 1, 8)]


@pytest.mark.parametrize("B,T,H,P,G,N", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba2_ssd_kernel(B, T, H, P, G, N, dtype):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = (jax.random.normal(ks[3], (B, T, G, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, T, G, N)) * 0.5).astype(dtype)
    s0 = jax.random.normal(ks[5], (B, H, N, P)) * 0.1
    y, sT = ops.mamba2_ssd(x, dt, A, Bm, Cm, s0, chunk=32, interpret=True)
    expect, sT_ref = ref.ssd_ref(x, dt, A, Bm, Cm, s0)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_model_chunked_paths_match_oracles():
    """The model-side chunked formulations agree with the same oracles the
    kernels are tested against (one ground truth for everything)."""
    from repro.models.rwkv6 import wkv_chunked
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(KEY, 6)
    B, T, H, N = 2, 96, 2, 64
    r = jax.random.normal(ks[0], (B, T, H, N)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, N)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, N)) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, N)) * 0.5 - 2.0))
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, N, N)) * 0.1
    o1, _ = wkv_chunked(r, k, v, w, u, s0, chunk=32)
    o2, _ = ref.wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)

    P, G, Nn = 16, 1, 8
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, Nn)) * 0.5
    Cm = jax.random.normal(ks[4], (B, T, G, Nn)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, Nn, P)) * 0.1
    y1, _ = ssd_chunked(x, dt, A, Bm, Cm, s0, chunk=32)
    y2, _ = ref.ssd_ref(x, dt, A, Bm, Cm, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_flash_attention_in_model_path():
    """attn_impl='pallas_interpret' end-to-end equals 'reference'."""
    from repro.models.config import get_config
    from repro.models import transformer as T
    cfg = get_config("stablelm-12b", smoke=True)
    B, S = 1, 64
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    params = T.init_params(cfg, KEY, jnp.float32)
    cfg_ref = cfg.replace(attn_impl="reference")
    cfg_pl = cfg.replace(attn_impl="pallas_interpret")
    la, _ = T.prefill(T.cast_for_compute(params), cfg_ref, tokens)
    lb, _ = T.prefill(T.cast_for_compute(params), cfg_pl, tokens)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               atol=0.08, rtol=0.05)


# ---- the seismic leapfrog stencil ---------------------------------------- #

def _seismic_rehearsal(**kw):
    """The Marmousi sweep's rehearsal sizes: 64 x 192 at 12 m, a 16-cell
    pad (a 96 x 224 padded grid)."""
    from repro.apps.seismic.solver import SeismicConfig
    base = dict(nx=192, nz=64, nt=24, dx=12.0, dt=0.0015, n_receivers=24,
                damp_width=16, damp_strength=0.1, pad=16, source_z=2,
                receiver_z=3, c_max=5500.0, model="marmousi")
    return SeismicConfig(**{**base, **kw})


def _seismic_fields(cfg, shots=None, seed=0):
    """u_prev and u (random, (shots,) + padded grid) and c of the padded
    model, as the forward builds it."""
    from repro.apps.seismic.solver import make_velocity_model
    rng = np.random.default_rng(seed)
    shape = cfg.padded_shape if shots is None else (shots,) + cfg.padded_shape
    u_prev, u = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for _ in range(2))
    vel = jnp.pad(make_velocity_model(cfg, "true", seed), cfg.pad,
                  mode="edge")
    return u_prev, u, (vel * cfg.dt) ** 2 / cfg.dx ** 2


def _stencil_step(cfg, lay, u_prev, u, c, sz, sx, amp):
    from repro.apps.seismic.solver import _damping_profile
    from repro.kernels import seismic_stencil as ks
    return ks.leapfrog(ks.embed(u_prev, lay), ks.embed(u, lay),
                       ks.embed(c, lay), sz, sx, amp, lay=lay,
                       profile=tuple(map(float, _damping_profile(cfg))))


def _close_to_float32_roundoff(got, want):
    """Equal to within 16 float32 ulps of the field's largest value."""
    scale = float(np.max(np.abs(np.asarray(want))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=16 * np.finfo(np.float32).eps * scale)


@pytest.mark.parametrize("blocks", ["one", "three"])
@pytest.mark.parametrize("source", [(0, 0), (-1, -1), (0, -1), (-1, 0),
                                    (37, 101)])
def test_seismic_stencil_step_matches_the_jnp_step(blocks, source):
    """One kernel step (interpret mode) against the jnp step at the
    rehearsal size, a source in each corner of the padded grid so that
    both wraps meet it, in the layout's one row block and in three: the
    grid equal to float32 round-off, the ghosts exact copies, every other
    cell outside the grid zero."""
    from repro.apps.seismic.solver import _damping, leapfrog_step
    from repro.kernels import seismic_stencil as ks
    cfg = _seismic_rehearsal()
    nzp, nxp = cfg.padded_shape
    lay = (ks.layout(nzp, nxp) if blocks == "one"
           else ks.Layout(nzp, nxp, rows=120, cols=256, block=40))
    sz, sx = source[0] % nzp, source[1] % nxp
    u_prev, u, c = _seismic_fields(cfg)
    got = _stencil_step(cfg, lay, u_prev, u, c, sz, sx, 0.75)
    want = leapfrog_step(u_prev, u, c, _damping(cfg), sz, sx, 0.75)
    _close_to_float32_roundoff(ks.crop(got, lay), want)
    assert np.array_equal(np.asarray(got),
                          np.asarray(ks.embed(ks.crop(got, lay), lay)))


@pytest.mark.parametrize("nesting", [(3,), (2, 3)])
def test_seismic_stencil_vmaps_over_shots_as_one_kernel(nesting):
    """vmapped over shots with different source columns (and over a second
    axis too), the step is one kernel whose grid has every shot on its
    inner axis, and each shot's field equals its single-shot step."""
    from repro.kernels import seismic_stencil as ks
    cfg = _seismic_rehearsal()
    nzp, nxp = cfg.padded_shape
    lay = ks.layout(nzp, nxp)
    shots = int(np.prod(nesting))
    u_prev, u, c = _seismic_fields(cfg, shots)
    cols = jnp.asarray([0, 57, nxp - 1, 3, 100, 200][:shots], jnp.int32)

    def one(a, b, sx):
        return _stencil_step(cfg, lay, a, b, c, 2, sx, 0.5)

    batched = one
    for _ in nesting:
        batched = jax.vmap(batched)
    args = [x.reshape(nesting + x.shape[1:]) for x in (u_prev, u, cols)]
    got = jax.jit(batched)(*args).reshape(shots, lay.rows, lay.cols)
    for k in range(shots):
        assert np.array_equal(np.asarray(got[k]),
                              np.asarray(one(u_prev[k], u[k], cols[k])))
    text = str(jax.make_jaxpr(batched)(*args))
    assert text.count("pallas_call") == 1
    assert f"grid=({lay.rows // lay.block}, {shots})" in text


@pytest.mark.parametrize("record_every,nt", [(1, 25), (3, 27), (4, 24)])
def test_forward_through_the_stencil_kernel_matches_the_jnp_forward(
        monkeypatch, record_every, nt):
    """Whole forwards of three shots, vmapped as the sweep runs them, with
    the kernel step (interpret mode) and with the jnp step: the same
    seismograms to float32 round-off, for a record every step, every
    third (an odd count of steps a loop iteration) and every fourth."""
    from repro.apps.seismic import solver
    cfg = _seismic_rehearsal(nt=nt, record_every=record_every)
    vel = solver.make_velocity_model(cfg, "init", 1)
    sources = jnp.asarray([0, 90, cfg.nx - 1], jnp.int32)
    receivers = jnp.asarray(np.stack([np.arange(0, 192, 8)] * 3), jnp.int32)

    def forwards():
        return jax.jit(jax.vmap(lambda s, r: solver.forward_simulation(
            vel, s, cfg, r)))(sources, receivers)

    want = forwards()
    monkeypatch.setattr(solver, "stencil_path", lambda cfg: "pallas")
    got = forwards()
    assert got.shape == want.shape == (3, nt // record_every, 24)
    _close_to_float32_roundoff(got, want)
    assert float(jnp.max(jnp.abs(want))) > 0


def test_stencil_kernel_gradient_matches_the_jnp_gradient(monkeypatch):
    """The kernel step's custom VJP (the jnp step's VJP at the saved
    inputs): the misfit's gradient in the velocity through the kernel
    path equals jax.grad of the jnp forward."""
    from repro.apps.seismic import solver
    cfg = _seismic_rehearsal(nt=24, record_every=2)
    observed = solver.forward_simulation(
        solver.make_velocity_model(cfg, "true", 1), 90, cfg)
    vel = solver.make_velocity_model(cfg, "init", 1)

    def grad():
        return jax.jit(jax.grad(solver.misfit), static_argnums=(2, 3))(
            vel, observed, 90, cfg)

    want = grad()
    monkeypatch.setattr(solver, "stencil_path", lambda cfg: "pallas")
    got = grad()
    assert float(jnp.max(jnp.abs(want))) > 0
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)
