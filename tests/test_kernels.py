"""Per-kernel validation: shape/dtype sweeps, Pallas interpret=True vs the
pure-jnp ref.py oracles (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    batched_ssm_scan,
    flash_attention,
    grouped_flash_attention,
    gt_update_2d,
    make_gt_update_fn,
    ref,
    ssm_scan,
)

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


# ---------------------------------------------------------------- ssm_scan
class TestSsmScan:
    @pytest.mark.parametrize("S,D,N", [(64, 128, 16), (128, 128, 8), (256, 256, 16)])
    @pytest.mark.parametrize("chunk", [32, 64])
    def test_matches_ref(self, S, D, N, chunk):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        # decay in (0, 1) for stability, like exp(-softplus) in mamba
        da = jax.nn.sigmoid(jax.random.normal(k1, (S, D, N))) * 0.95
        dbx = jax.random.normal(k2, (S, D, N)) * 0.1
        c = jax.random.normal(k3, (S, N))
        got = ssm_scan(da, dbx, c, chunk=chunk, interpret=True)
        want, _ = ref.ssm_scan_ref(da, dbx, c, jnp.zeros((D, N)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )

    def test_chunk_invariance(self):
        """Carried state across chunk boundaries: result must not depend on
        the chunk size."""
        S, D, N = 128, 128, 16
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        da = jax.nn.sigmoid(jax.random.normal(k1, (S, D, N))) * 0.9
        dbx = jax.random.normal(k2, (S, D, N)) * 0.1
        c = jax.random.normal(k3, (S, N))
        y32 = ssm_scan(da, dbx, c, chunk=32, interpret=True)
        y128 = ssm_scan(da, dbx, c, chunk=128, interpret=True)
        np.testing.assert_allclose(
            np.asarray(y32), np.asarray(y128), rtol=1e-5, atol=1e-5
        )

    def test_batched_wrapper(self):
        B, S, D, N = 2, 64, 128, 8
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
        da = jax.nn.sigmoid(jax.random.normal(k1, (B, S, D, N))) * 0.9
        dbx = jax.random.normal(k2, (B, S, D, N)) * 0.1
        c = jax.random.normal(k3, (B, S, N))
        got = batched_ssm_scan(da, dbx, c, chunk=32)
        for b in range(B):
            want, _ = ref.ssm_scan_ref(da[b], dbx[b], c[b], jnp.zeros((D, N)))
            np.testing.assert_allclose(
                np.asarray(got[b]), np.asarray(want), rtol=1e-4, atol=1e-4
            )
