"""Fused pack/unpack payload kernel conformance (deliverable: ISSUE 3).

Three layers of agreement, all on CPU via interpret=True:

  * pack kernel vs oracle — `pack_payload_2d` (Pallas) against
    `ref.pack_payload_ref` on lane-aligned shapes, fp32 / bf16 / fp64,
    topk / randk, every encoding, with and without feedback: packed
    uint32 words and indices agree BITWISE (they are integer pipelines),
    scales and residuals to <= 1e-6 (the kernel compiles as one XLA unit
    whose fusion may round the float math differently);
  * unpack kernel vs oracle — `unpack_payload_2d` against
    `ref.decode_payload_ref` on the same payloads;
  * word packing algebra — pack_words/unpack_words round-trip bitwise
    for every storage width, including non-power-of-two bit requests
    that pad up to the next sub-word width;
  * dispatcher — `encode_leaf(use_kernel=True)` takes the fused path
    exactly on lane-aligned leaves with results interchangeable with
    the oracle path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed.transport import LeafSpec, decode_leaf, encode_leaf
from repro.kernels import pack_payload_2d, ref, unpack_payload_2d

pytestmark = pytest.mark.kernel  # Pallas interpret-mode suite

F32, F64, BF16 = jnp.float32, jnp.float64, jnp.bfloat16
ALIGNED = [(1, 128), (4, 128), (6, 256), (3, 384)]


def _spec(R, C, ratio, bits, mode="topk"):
    return dataclasses.replace(
        LeafSpec.build((C,), F32, ratio, bits, mode), rows=R
    )


def _inputs(shape, dtype, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    c = jax.random.normal(k1, shape, dtype)
    e = (0.1 * jax.random.normal(k2, shape)).astype(dtype)
    u_sel = jax.random.uniform(k3, shape)
    u_rnd = jax.random.uniform(k4, shape)
    return c, e, u_sel, u_rnd


def _run_both(c, e, u_sel, u_rnd, spec):
    kw = dict(k=spec.k, bits=spec.bits, mode=spec.mode,
              encoding=spec.encoding)
    got = pack_payload_2d(
        c, e, u_sel, u_rnd,
        index_dtype=spec.index_dtype, scale_dtype=spec.scale_dtype,
        interpret=True, **kw,
    )
    want = ref.pack_payload_ref(
        c, e, u_sel, u_rnd, index_dtype=spec.index_dtype, **kw
    )
    return got, want


def _assert_match(got, want, spec, atol=1e-6):
    data_g, idx_g, scale_g, res_g = got
    data_w, idx_w, scale_w, res_w = want
    if spec.encoding == "quant":  # uint32 words: bitwise
        np.testing.assert_array_equal(np.asarray(data_g), np.asarray(data_w))
    else:
        np.testing.assert_allclose(
            np.asarray(data_g, np.float64), np.asarray(data_w, np.float64),
            rtol=0, atol=atol,
        )
    np.testing.assert_array_equal(np.asarray(idx_g), np.asarray(idx_w))
    assert idx_g.dtype == spec.index_dtype
    np.testing.assert_allclose(
        np.asarray(scale_g, np.float64), np.asarray(scale_w, np.float64),
        rtol=0, atol=atol,
    )
    np.testing.assert_allclose(
        np.asarray(res_g, np.float64), np.asarray(res_w, np.float64),
        rtol=0, atol=atol,
    )


# --------------------------------------------------- pack kernel vs oracle
class TestPackKernelMatchesReference:
    @pytest.mark.parametrize("shape", ALIGNED)
    @pytest.mark.parametrize("dtype", [F32, BF16])
    @pytest.mark.parametrize("mode", ["topk", "randk"])
    @pytest.mark.parametrize("bits", [32, 8, 4])
    def test_matches_ref(self, shape, dtype, mode, bits):
        c, e, u_sel, u_rnd = _inputs(shape, dtype)
        spec = dataclasses.replace(
            LeafSpec.build((shape[1],), dtype, 1 / 3, bits, mode),
            rows=shape[0],
        )
        got, want = _run_both(c, e, u_sel, u_rnd, spec)
        _assert_match(got, want, spec)

    @pytest.mark.parametrize("shape", [(4, 128), (6, 256)])
    def test_matches_ref_float64(self, shape):
        c, e, u_sel, u_rnd = _inputs(shape, F64)
        spec = dataclasses.replace(
            LeafSpec.build((shape[1],), F64, 0.25, 8), rows=shape[0]
        )
        got, want = _run_both(c, e, u_sel, u_rnd, spec)
        _assert_match(got, want, spec, atol=1e-12)

    @pytest.mark.parametrize(
        "encoding", ["dense", "sparse", "quant", "quant_dense"]
    )
    def test_every_encoding(self, encoding):
        c, e, u_sel, u_rnd = _inputs((4, 256), F32, seed=1)
        spec = dataclasses.replace(
            _spec(4, 256, 0.25, 8), encoding=encoding
        )
        got, want = _run_both(c, e, u_sel, u_rnd, spec)
        _assert_match(got, want, spec)

    def test_no_feedback_path(self):
        c, _, u_sel, u_rnd = _inputs((4, 256), F32, seed=2)
        spec = _spec(4, 256, 0.25, 8)
        got, want = (
            pack_payload_2d(
                c, None, u_sel, u_rnd, k=spec.k, bits=8,
                index_dtype=spec.index_dtype, interpret=True,
            ),
            ref.pack_payload_ref(
                c, None, u_sel, u_rnd, k=spec.k, bits=8,
                index_dtype=spec.index_dtype,
            ),
        )
        _assert_match(got, want, spec)

    def test_block_rows_invariance(self):
        c, e, u_sel, u_rnd = _inputs((8, 256), F32, seed=3)
        spec = _spec(8, 256, 0.25, 8)
        kw = dict(k=spec.k, bits=8, index_dtype=spec.index_dtype)
        a = pack_payload_2d(
            c, e, u_sel, u_rnd, block_rows=8, interpret=True, **kw
        )
        b = pack_payload_2d(
            c, e, u_sel, u_rnd, block_rows=2, interpret=True, **kw
        )
        for g, w in zip(a, b):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_indices_sorted_and_exact_k(self):
        """Indices come out ascending with exactly k per row, even under
        ties (all-equal and all-zero rows)."""
        c = jnp.concatenate(
            [jnp.ones((1, 128)), jnp.zeros((1, 128)),
             -jnp.ones((1, 128))]
        ).astype(F32)
        spec = _spec(3, 128, 0.25, 32)
        _, idx, _, _ = pack_payload_2d(
            c, None, None, None, k=spec.k, bits=32, encoding="sparse",
            index_dtype=spec.index_dtype, interpret=True,
        )
        idx = np.asarray(idx)
        assert idx.shape == (3, 32)
        for row in idx:
            assert np.all(np.diff(row) > 0)  # strictly ascending, unique
            assert row.min() >= 0 and row.max() < 128


# ------------------------------------------------- unpack kernel vs oracle
class TestUnpackKernelMatchesReference:
    @pytest.mark.parametrize("dtype", [F32, BF16, F64])
    @pytest.mark.parametrize("bits", [8, 4])
    def test_matches_ref(self, dtype, bits):
        c, e, u_sel, u_rnd = _inputs((4, 256), dtype, seed=4)
        spec = dataclasses.replace(
            LeafSpec.build((256,), dtype, 0.25, bits), rows=4
        )
        data, idx, scale, _ = ref.pack_payload_ref(
            c, e, u_sel, u_rnd, k=spec.k, bits=bits,
            encoding=spec.encoding, index_dtype=spec.index_dtype,
        )
        kw = dict(cols=256, dtype=dtype, k=spec.k, bits=bits,
                  encoding=spec.encoding)
        got = unpack_payload_2d(data, idx, scale, interpret=True, **kw)
        want = ref.decode_payload_ref(data, idx, scale, **kw)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=0, atol=1e-6,
        )

    def test_fused_round_trip_equals_dense_compress(self):
        """encode(kernel) -> decode(kernel) reproduces the dense fused
        compress kernel's chat to <= 1 ulp on the same draws."""
        from repro.kernels import compress_correction_2d

        c, e, u_sel, u_rnd = _inputs((4, 256), F32, seed=5)
        spec = _spec(4, 256, 0.25, 8)
        payload, resid = encode_leaf(c, e, u_sel, u_rnd, spec, use_kernel=True)
        decoded = decode_leaf(payload, spec, use_kernel=True)
        chat, resid_dense = compress_correction_2d(
            c, e, u_sel, u_rnd, k=spec.k, bits=8, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(decoded, np.float64), np.asarray(chat, np.float64),
            rtol=0, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(resid, np.float64),
            np.asarray(resid_dense, np.float64),
            rtol=0, atol=1e-6,
        )


# ------------------------------------------------------ word pack algebra
class TestWordPacking:
    @pytest.mark.parametrize("bits", [2, 3, 4, 6, 8, 12, 16])
    @pytest.mark.parametrize("k", [1, 7, 8, 31, 32, 100])
    def test_pack_unpack_round_trip_bitwise(self, bits, k):
        sb = ref.storage_bits(bits)
        assert sb in (2, 4, 8, 16, 32) and sb >= bits
        levels = jax.random.randint(
            jax.random.PRNGKey(bits * 101 + k), (5, k), 0, 2**bits - 1
        ).astype(jnp.uint32)
        words = ref.pack_words(levels, bits)
        assert words.dtype == jnp.uint32
        assert words.shape == (5, ref.word_layout(k, bits)[2])
        back = ref.unpack_words(words, k, bits)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(levels))

    def test_word_budget_is_tight(self):
        # 8-bit levels: 4 per word; 9 levels -> 3 words, not 9 bytes
        assert ref.word_layout(9, 8) == (8, 4, 3)
        # 3-bit levels store at 4 bits: 8 per word
        assert ref.word_layout(16, 3) == (4, 8, 2)
        assert ref.storage_bits(17) == 32


# ------------------------------------------------------------- dispatcher
class TestDispatcher:
    @pytest.mark.parametrize("C,fused", [(128, True), (256, True),
                                         (100, False), (37, False)])
    def test_kernel_dispatch_by_alignment(self, C, fused, monkeypatch):
        import repro.fed.transport as tr

        calls = {"kernel": 0}
        orig = tr.pack_payload_2d

        def spy(*a, **k):
            calls["kernel"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(tr, "pack_payload_2d", spy)
        c, e, u_sel, u_rnd = _inputs((2, C), F32, seed=6)
        spec = dataclasses.replace(
            LeafSpec.build((C,), F32, 0.5, 8), rows=2
        )
        fusedp, _ = tr.encode_leaf(c, e, u_sel, u_rnd, spec, use_kernel=True)
        assert calls["kernel"] == (1 if fused else 0)
        plain, _ = tr.encode_leaf(c, e, u_sel, u_rnd, spec, use_kernel=False)
        for a, b in zip(fusedp, plain):
            if a is None:
                assert b is None
                continue
            if a.dtype == jnp.uint32 or "int" in str(a.dtype):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:
                np.testing.assert_allclose(
                    np.asarray(a, np.float64), np.asarray(b, np.float64),
                    rtol=0, atol=1e-6,
                )
