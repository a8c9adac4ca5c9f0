"""Per-kernel validation: shape/dtype sweeps, Pallas interpret=True vs the
pure-jnp ref.py oracles (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    backend,
    flash_attention,
    grouped_flash_attention,
    gt_update_2d,
    make_gt_update_fn,
    ref,
    selective_scan,
)
from repro.models.mamba import _mamba1_scan, init_mamba, mamba_block

pytestmark = pytest.mark.kernel  # Pallas interpret-mode suite

F32, BF16 = jnp.float32, jnp.bfloat16


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == BF16 else dict(rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ gt_update
class TestGtUpdate:
    @pytest.mark.parametrize("shape", [(8, 128), (256, 128), (128, 512), (512, 384)])
    @pytest.mark.parametrize("dtype", [F32, BF16])
    @pytest.mark.parametrize("sign", [-1.0, 1.0])
    def test_matches_ref(self, shape, dtype, sign):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        z = jax.random.normal(k1, shape, dtype)
        g = jax.random.normal(k2, shape, dtype)
        c = jax.random.normal(k3, shape, dtype)
        eta = 3e-3
        got = gt_update_2d(
            z, g, c, eta=eta, sign=sign,
            block_rows=min(128, shape[0]), interpret=True,
        )
        want = ref.gt_update_ref(z, g, c, eta, sign)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_fp8_correction_dtype(self):
        """The beyond-paper fp8 correction storage must flow through the
        kernel (cast up inside, result dtype = param dtype)."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        z = jax.random.normal(k1, (128, 128), F32)
        g = jax.random.normal(k2, (128, 128), F32)
        c = jax.random.normal(k3, (128, 128), F32).astype(jnp.float8_e4m3fn)
        got = gt_update_2d(z, g, c, eta=1e-2, sign=-1.0, interpret=True)
        want = ref.gt_update_ref(z, g, c, 1e-2, -1.0)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_pytree_wrapper_handles_ragged_sizes(self):
        """make_gt_update_fn pads non-multiple-of-128 leaves; values must be
        identical to the oracle on every leaf."""
        key = jax.random.PRNGKey(2)
        ks = jax.random.split(key, 9)
        tree_shape = [(17,), (3, 5), (130, 7)]
        z = {f"l{i}": jax.random.normal(ks[i], s) for i, s in enumerate(tree_shape)}
        g = {f"l{i}": jax.random.normal(ks[3 + i], s) for i, s in enumerate(tree_shape)}
        c = {f"l{i}": jax.random.normal(ks[6 + i], s) for i, s in enumerate(tree_shape)}
        upd = make_gt_update_fn(use_kernel=True)
        got = upd(z, g, c, 1e-2, 1.0)
        for kname in z:
            want = ref.gt_update_ref(z[kname], g[kname], c[kname], 1e-2, 1.0)
            np.testing.assert_allclose(
                np.asarray(got[kname]), np.asarray(want), rtol=1e-6, atol=1e-6
            )
            assert got[kname].shape == z[kname].shape


# ------------------------------------------------------------ flash_attention
class TestFlashAttention:
    @pytest.mark.parametrize("Sq,Skv", [(128, 128), (256, 256), (128, 384)])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", [F32, BF16])
    def test_matches_ref(self, Sq, Skv, causal, dtype):
        if causal and Sq != Skv:
            pytest.skip("causal with Sq<Skv is the cache case, covered below")
        B, H, hd = 1, 2, 64
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (B, H, Sq, hd), dtype)
        k = jax.random.normal(kk, (B, H, Skv, hd), dtype)
        v = jax.random.normal(kv, (B, H, Skv, hd), dtype)
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    @pytest.mark.parametrize("window", [128, 256])
    def test_sliding_window(self, window):
        B, H, S, hd = 1, 2, 512, 64
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(kq, (B, H, S, hd), F32)
        k = jax.random.normal(kk, (B, H, S, hd), F32)
        v = jax.random.normal(kv, (B, H, S, hd), F32)
        got = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_logit_softcap(self):
        B, H, S, hd = 1, 1, 256, 64
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
        q = 4.0 * jax.random.normal(kq, (B, H, S, hd), F32)
        k = 4.0 * jax.random.normal(kk, (B, H, S, hd), F32)
        v = jax.random.normal(kv, (B, H, S, hd), F32)
        got = flash_attention(q, k, v, causal=True, softcap=50.0, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, softcap=50.0)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )
        # and the capped result differs from the uncapped one
        uncapped = ref.flash_attention_ref(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(want - uncapped))) > 1e-3

    @pytest.mark.parametrize("block_q,block_kv", [(64, 128), (128, 64), (64, 64)])
    def test_block_shape_invariance(self, block_q, block_kv):
        """The result must not depend on the BlockSpec tiling."""
        B, H, S, hd = 1, 1, 256, 64
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(kq, (B, H, S, hd), F32)
        k = jax.random.normal(kk, (B, H, S, hd), F32)
        v = jax.random.normal(kv, (B, H, S, hd), F32)
        got = flash_attention(
            q, k, v, causal=True, block_q=block_q, block_kv=block_kv,
            interpret=True,
        )
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_gqa_adapter(self):
        """grouped_flash_attention repeats KV groups and restores layout."""
        B, S, H, KV, hd = 2, 128, 8, 2, 64
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(kq, (B, S, H, hd), F32)
        k = jax.random.normal(kk, (B, S, KV, hd), F32)
        v = jax.random.normal(kv, (B, S, KV, hd), F32)
        got = grouped_flash_attention(q, k, v, causal=True)
        assert got.shape == (B, S, H, hd)
        G = H // KV
        kt = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
        vt = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
        want = ref.flash_attention_ref(
            q.transpose(0, 2, 1, 3), kt, vt, causal=True
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------- selective_scan
def _scan_inputs(seed, Bt, S, D, N):
    """Mamba-1-like inputs: dt = softplus(.) > 0, A = -exp(A_log) < 0."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jax.nn.softplus(jax.random.normal(k[0], (Bt, S, D), F32) - 1.0)
    x = jax.random.normal(k[1], (Bt, S, D), F32)
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (D, N), F32))
    Bc = jax.random.normal(k[3], (Bt, S, N), F32)
    Cc = jax.random.normal(k[4], (Bt, S, N), F32)
    return dt, x, A, Bc, Cc


def _jnp_scan(dt, x, A, Bc, Cc, chunk=32):
    """The model's jnp path (`models/mamba._chunked_scan`), zero state."""
    state0 = jnp.zeros((dt.shape[0], A.shape[0], 1, A.shape[1]), F32)
    return _mamba1_scan(dt, x, A, Bc, Cc, state0=state0, chunk=chunk)[0]


def _grads(fn, args, seed=7):
    """Gradients of <fn(args), w> in all five inputs for a fixed random w."""
    w = jax.random.normal(jax.random.PRNGKey(seed), args[0].shape, F32)
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(*args)


def _close(got, want, tol=2e-5):
    """Equal to `tol` of the largest entry of `want`."""
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0, atol=tol * scale
    )


class TestSelectiveScan:
    @pytest.mark.parametrize("S,D,N", [(64, 128, 16), (128, 128, 8), (256, 256, 16)])
    @pytest.mark.parametrize("chunk", [32, 64])
    def test_matches_ref(self, S, D, N, chunk):
        """Forward parity with the sequential oracle and the jnp path."""
        args = _scan_inputs(0, 1, S, D, N)
        got = selective_scan(*args, chunk=chunk, interpret=True)
        want, _ = ref.ssm_scan_ref(*args)
        assert got.shape == (1, S, D) and got.dtype == F32
        _close(got, want)
        _close(got, _jnp_scan(*args))

    @pytest.mark.parametrize("S,D,chunk,block_d", [
        (64, 256, 16, 128),   # 4 chunks, 2 d-blocks
        (96, 256, 32, 256),   # 3 chunks, 1 d-block
    ])
    def test_gradients_match_jnp_path(self, S, D, chunk, block_d):
        """d/d(dt, x, A, B, C) of the custom VJP against jax.grad of the
        model's jnp scan, across several chunks and d-blocks."""
        args = _scan_inputs(1, 2, S, D, 16)
        got = _grads(lambda *a: selective_scan(
            *a, chunk=chunk, block_d=block_d, interpret=True), args)
        want = _grads(_jnp_scan, args)
        for g, w in zip(got, want):
            assert g.shape == w.shape
            _close(g, w, tol=5e-5)

    @staticmethod
    def _tiled(chunk, block_d, args):
        f = lambda *a: selective_scan(  # noqa: E731
            *a, chunk=chunk, block_d=block_d, interpret=True)
        return (f(*args),) + _grads(f, args)

    @pytest.fixture(scope="class")
    def one_tile(self):
        """Inputs, and y with every gradient under one chunk and one
        d-block."""
        args = _scan_inputs(2, 1, 64, 256, 16)
        return args, self._tiled(64, 256, args)

    @pytest.mark.parametrize("chunk,block_d", [
        (16, 128), (32, 256), (64, 128),
    ])
    def test_chunk_invariance(self, chunk, block_d, one_tile):
        """The carried state and adjoint across chunk and d-block
        boundaries: y and every gradient match the one-chunk, one-block
        tiling."""
        args, want = one_tile
        for g, w in zip(self._tiled(chunk, block_d, args), want):
            _close(g, w, tol=1e-5)

    def test_mamba_block_takes_the_kernel(self, monkeypatch):
        """A mamba1 block built as for one TPU runs the kernel (here
        interpreted) in place of the jnp scan: the same output and the
        same parameter gradients."""
        params = init_mamba(jax.random.PRNGKey(3), 64, 128, 16, 4,
                            "mamba1", F32)
        u = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64), F32)
        w = jax.random.normal(jax.random.PRNGKey(5), u.shape, F32)

        def loss(p):
            out, cache = mamba_block(p, u, variant="mamba1", d_state=16)
            assert cache is None
            return jnp.sum(out * w)

        def run():
            jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
            return ("pallas_call" in jaxpr, jax.value_and_grad(loss)(params))

        jnp_path, (want, gwant) = run()
        monkeypatch.setattr(backend, "on_one_tpu", lambda: True)
        kernel, (got, ggot) = run()
        assert kernel and not jnp_path
        _close(got, want)
        for k in gwant:
            _close(ggot[k], gwant[k], tol=5e-5)

    def test_vmap_over_agents(self):
        """The round's vmap(grad) over a leading agent axis: the batched
        kernels (an extra grid axis) equal one call per agent."""
        m = 2
        args = [jnp.stack(u) for u in zip(
            *(_scan_inputs(10 + i, 1, 32, 128, 16) for i in range(m)))]
        f = lambda *a: selective_scan(*a, chunk=16, interpret=True)  # noqa: E731
        y = jax.vmap(f)(*args)
        g = jax.vmap(lambda *a: _grads(f, a))(*args)
        for i in range(m):
            one = [u[i] for u in args]
            _close(y[i], f(*one))
            for gb, g1 in zip(g, _grads(f, one)):
                _close(gb[i], g1, tol=1e-6)
