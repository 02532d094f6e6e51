"""Dense latent attention over the paged latent cache
(kernels/primitives/mla.py) and the tower's bidirectional attention
(kernels/primitives/vit.py): each Pallas body, interpreted, against its
XLA form, and the two forms of latent attention against each other
(head space = latent space) at page and step edges, for the decode row
(T = 1) and the chunk (T = 8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import primitives as prims
from paddle_tpu.kernels.primitives import mla

H, NOPE, ROPE, C, V, PAGE, W = 4, 16, 8, 32, 16, 4, 128


def _case(seed, starts, t, max_pages, dtype=jnp.float32):
    """A pool whose sequences hold random rows up to their lengths, in
    pages scattered over the pool, and random queries and matrices."""
    rng = np.random.RandomState(seed)
    b = len(starts)
    num_pages = b * max_pages + 1
    pool = rng.normal(0, 1, (num_pages, PAGE, W)).astype(np.float32)
    pool[..., C + ROPE:] = 0.0          # the pad lanes hold zeros
    order = rng.permutation(np.arange(1, num_pages))
    table = np.zeros((b, max_pages), np.int32)
    for i, start in enumerate(starts):
        used = -(-(start + t) // PAGE)
        table[i, :used] = order[i * max_pages:i * max_pages + used]
    f = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)
    return dict(
        q_nope=f(b, t, H, NOPE), q_rope=f(b, t, H, ROPE),
        w_uk=f(H, NOPE, C) * C ** -0.5, w_uv=f(H, C, V) * C ** -0.5,
        pool=jnp.asarray(pool, dtype), table=jnp.asarray(table),
        q_start=jnp.asarray(starts, jnp.int32),
        scale=float(NOPE + ROPE) ** -0.5)


def _latent(z, force):
    q_lat = jnp.einsum("bthn,hnc->bthc", z["q_nope"], z["w_uk"])
    o = prims.paged_mla_attention(q_lat, z["q_rope"], z["pool"], z["table"],
                                  z["q_start"], sm_scale=z["scale"],
                                  force=force)
    return jnp.einsum("bthc,hcv->bthv", o, z["w_uv"])


def _head(z, force):
    return prims.mla_chunk_attention(
        z["q_nope"], z["q_rope"], z["pool"], z["table"], z["q_start"],
        z["w_uk"], z["w_uv"], sm_scale=z["scale"], force=force)


# starts at page edges (0, PAGE - 1, PAGE, a step's last key and the one
# after it) and in the middle; 20 pages = 3 grid steps at 8 pages a step
@pytest.mark.parametrize("t,starts", [
    (1, (0, 3, 4, 31, 32, 50, 78)),
    (8, (0, 4, 24, 25, 40, 71)),
])
def test_both_forms_agree_with_their_xla_forms_and_each_other(
        t, starts, monkeypatch):
    monkeypatch.setattr(mla, "KEYS_PER_STEP", 8 * PAGE)
    with jax.default_matmul_precision("highest"):
        z = _case(7 + t, starts, t, max_pages=20)
        want = _latent(z, "reference")
        for got in (_latent(z, "pallas"), _head(z, "reference"),
                    _head(z, "pallas")):
            assert got.shape == (len(starts), t, H, V)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)


def test_rows_past_a_sequence_are_never_scored(monkeypatch):
    monkeypatch.setattr(mla, "KEYS_PER_STEP", 2 * PAGE)
    z = _case(3, (5, 9), 1, max_pages=8)
    poisoned = z["pool"].at[0].set(1e4)      # the trash page, and
    used = np.asarray(z["table"])
    live = {int(p) for p in used.reshape(-1) if p}
    for p in range(1, z["pool"].shape[0]):   # every page no table names
        if p not in live:
            poisoned = poisoned.at[p].set(1e4)
    for fn in (_latent, _head):
        np.testing.assert_allclose(
            np.asarray(fn(dict(z, pool=poisoned), "pallas")),
            np.asarray(fn(z, "pallas")), rtol=1e-6, atol=1e-6)


def test_a_bfloat16_pool_rounds_where_the_xla_form_rounds():
    z = _case(11, (13, 30), 8, max_pages=10, dtype=jnp.bfloat16)
    for fn in (_latent, _head):
        np.testing.assert_allclose(
            np.asarray(fn(z, "pallas")), np.asarray(fn(z, "reference")),
            rtol=3e-2, atol=3e-2)


def test_neither_body_takes_a_mask_and_both_book_their_dispatch():
    import inspect

    z = _case(5, (6,), 8, max_pages=4)
    for fn, name in ((_latent, "paged_mla_attention"),
                     (_head, "mla_chunk_attention")):
        assert "selected" not in inspect.signature(
            getattr(prims, name)).parameters
        before = obs.snapshot().get("pt_kernel_dispatch_total", {}).get(
            "samples", {}).get((name, "interpret"), 0)
        fn(z, "pallas")
        after = obs.snapshot()["pt_kernel_dispatch_total"]["samples"]
        assert after[(name, "interpret")] > before
    with pytest.raises(ValueError, match="latent cache has shape"):
        prims.paged_mla_attention(
            jnp.zeros((1, 1, H, C)), jnp.zeros((1, 1, H, ROPE)),
            jnp.zeros((3, PAGE, C)), z["table"], z["q_start"], sm_scale=1.0)


@pytest.mark.parametrize("n,d", [(16, 12), (24, 16), (520, 16), (1100, 8)])
def test_vit_attention_against_its_xla_form(n, d):
    rng = np.random.RandomState(n)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (3, n, d)), jnp.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        want = prims.vit_attention(q, k, v, sm_scale=d ** -0.5,
                                   force="reference")
        got = prims.vit_attention(q, k, v, sm_scale=d ** -0.5,
                                  force="pallas")
    assert got.shape == (3, n, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
