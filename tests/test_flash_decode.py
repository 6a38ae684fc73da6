"""Fused flash-decode kernel vs the blockwise-walk oracle.

Runs the Pallas interpreter on CPU (same kernel code the TPU compiles,
minus Mosaic lowering — the on-chip benchmark exercises that). The walk
(`decode_attention`'s fori_loop schedule) is the oracle: the kernel exists
to remove its per-iteration overhead, not to change its math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_mpi_tpu.ops.attention import decode_attention
from deeplearning_mpi_tpu.ops.pallas.flash_decode import (
    decode_block_fits,
    flash_decode,
)


def _bufs(B=2, L=64, H=4, Hkv=None, D=16, idx=37, seed=0):
    """Cache buffers with the real cache's contract: unfilled rows zero."""
    Hkv = Hkv or H
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)).astype(np.float32))
    mask = (np.arange(L) <= idx)[None, :, None, None]
    k = jnp.asarray((rng.normal(size=(B, L, Hkv, D)) * mask).astype(np.float32))
    v = jnp.asarray((rng.normal(size=(B, L, Hkv, D)) * mask).astype(np.float32))
    return q, k, v


class TestFlashDecodeKernel:
    @pytest.mark.parametrize("idx", [0, 15, 16, 37, 63])
    @pytest.mark.parametrize("hkv", [4, 2, 1], ids=["mha", "gqa2", "mqa"])
    def test_matches_walk_at_every_fill(self, idx, hkv):
        q, k, v = _bufs(Hkv=hkv, idx=idx)
        ref = decode_attention(
            q, k, v, jnp.int32(idx), block=16, dense_max=0, use_kernel=False
        )
        out = flash_decode(q, k, v, jnp.int32(idx), block=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_blocks_past_boundary_never_read(self):
        """Poison every block past the boundary block with NaN: the clamped
        index map must revisit the boundary block instead of reading them
        (the O(index)-traffic property, testable in interpret mode as a
        NaN-freedom invariant)."""
        q, k, v = _bufs(B=1, L=64, idx=20)  # boundary block = rows 16..31
        k = np.array(k); v = np.array(v)  # writable copies
        k[:, 32:] = np.nan
        v[:, 32:] = np.nan
        out = flash_decode(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.int32(20), block=16, interpret=True,
        )
        assert np.all(np.isfinite(np.asarray(out)))

    def test_bf16_inputs(self):
        q, k, v = _bufs(idx=37)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ref = decode_attention(
            qb, kb, vb, jnp.int32(37), block=16, dense_max=0, use_kernel=False
        )
        out = flash_decode(qb, kb, vb, jnp.int32(37), block=16, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-2,
        )


class TestPerRowIndex:
    """[B]-shaped fill levels: the continuous-batching contract — every
    row clamps, gates, and masks against its OWN index."""

    def _ragged(self, idx, Hkv=2, L=64):
        B = len(idx)
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(B, 1, 4, 16)).astype(np.float32))
        mask = (np.arange(L)[None, :] <= np.asarray(idx)[:, None])[
            :, :, None, None
        ]
        k = jnp.asarray(
            (rng.normal(size=(B, L, Hkv, 16)) * mask).astype(np.float32)
        )
        v = jnp.asarray(
            (rng.normal(size=(B, L, Hkv, 16)) * mask).astype(np.float32)
        )
        return q, k, v

    @pytest.mark.parametrize("window", [None, 24])
    def test_kernel_matches_per_row_walk(self, window):
        """The kernel on an index VECTOR must equal running the scalar walk
        row by row — rows at different fills share one fixed-shape call."""
        idx = [0, 15, 37, 63]
        q, k, v = self._ragged(idx)
        ref = jnp.concatenate(
            [
                decode_attention(
                    q[b : b + 1], k[b : b + 1], v[b : b + 1],
                    jnp.int32(i), block=16, dense_max=0, use_kernel=False,
                    window=window,
                )
                for b, i in enumerate(idx)
            ],
            axis=0,
        )
        out = flash_decode(
            q, k, v, jnp.asarray(idx, jnp.int32), block=16, interpret=True,
            window=window,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_batched_dense_matches_per_row_walk(self):
        from deeplearning_mpi_tpu.ops.attention import (
            batched_decode_attention,
        )

        idx = [5, 37, 63]
        q, k, v = self._ragged(idx)
        ref = jnp.concatenate(
            [
                decode_attention(
                    q[b : b + 1], k[b : b + 1], v[b : b + 1],
                    jnp.int32(i), block=16, dense_max=0, use_kernel=False,
                )
                for b, i in enumerate(idx)
            ],
            axis=0,
        )
        out = batched_decode_attention(q, k, v, jnp.asarray(idx, jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("window", [None, 24])
    def test_inactive_row_outputs_zero(self, window):
        """index < 0 marks an empty serving slot: its output must be zeros
        (not a softmax-renormalized average of garbage V rows), and live
        rows must be unaffected by its presence."""
        from deeplearning_mpi_tpu.ops.attention import (
            batched_decode_attention,
        )

        q, k, v = self._ragged([5, 37, 63])
        full = batched_decode_attention(
            q, k, v, jnp.asarray([5, 37, 63], jnp.int32), window=window
        )
        mixed = batched_decode_attention(
            q, k, v, jnp.asarray([5, -1, 63], jnp.int32), window=window
        )
        assert np.all(np.asarray(mixed)[1] == 0.0)
        np.testing.assert_array_equal(np.asarray(mixed)[0], np.asarray(full)[0])
        np.testing.assert_array_equal(np.asarray(mixed)[2], np.asarray(full)[2])

    def test_wrong_index_shape_rejected(self):
        from deeplearning_mpi_tpu.ops.attention import (
            batched_decode_attention,
        )

        q, k, v = self._ragged([5, 37])
        with pytest.raises(ValueError, match="one fill level per row"):
            batched_decode_attention(q, k, v, jnp.zeros((3,), jnp.int32))
        with pytest.raises(ValueError, match="one fill level per row"):
            flash_decode(
                q, k, v, jnp.zeros((3,), jnp.int32), block=16, interpret=True
            )


class TestInt8KV:
    """int8 KV-cache variant: half the cache bytes, VMEM dequantization."""

    @pytest.mark.parametrize("hkv", [4, 2], ids=["mha", "gqa2"])
    @pytest.mark.parametrize("window", [None, 24])
    def test_matches_walk_on_dequantized_buffers(self, hkv, window):
        """The kernel on (int8, scales) must equal the walk on the
        DEQUANTIZED buffers — quantization error is quantize_kv's contract,
        not the kernel's; the kernel itself must be exact."""
        from deeplearning_mpi_tpu.ops.pallas.flash_decode import quantize_kv

        q, k, v = _bufs(Hkv=hkv, idx=50)
        k8, ks = quantize_kv(k)
        v8, vs = quantize_kv(v)
        k_dq = k8.astype(jnp.float32) * ks[..., None]
        v_dq = v8.astype(jnp.float32) * vs[..., None]
        ref = decode_attention(
            q, k_dq, v_dq, jnp.int32(50), block=16, dense_max=0,
            use_kernel=False, window=window,
        )
        out = flash_decode(
            q, k8, v8, jnp.int32(50), block=16, interpret=True,
            window=window, k_scale=ks, v_scale=vs,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_quantization_error_bounded(self):
        from deeplearning_mpi_tpu.ops.pallas.flash_decode import quantize_kv

        _, k, _ = _bufs(idx=63)
        k8, ks = quantize_kv(k)
        k_dq = np.asarray(k8, np.float32) * np.asarray(ks)[..., None]
        err = np.abs(k_dq - np.asarray(k))
        assert np.all(err <= np.asarray(ks)[..., None] / 2 + 1e-6)

    def test_scales_without_int8_rejected(self):
        from deeplearning_mpi_tpu.ops.pallas.flash_decode import quantize_kv

        q, k, v = _bufs(idx=20)
        _, ks = quantize_kv(k)
        with pytest.raises(ValueError, match="int8"):
            flash_decode(
                q, k, v, jnp.int32(20), block=16, interpret=True,
                k_scale=ks, v_scale=ks,
            )


class TestDispatcher:
    def test_use_kernel_true_matches_walk(self):
        q, k, v = _bufs(idx=50)
        walk = decode_attention(
            q, k, v, jnp.int32(50), block=16, dense_max=0, use_kernel=False
        )
        kern = decode_attention(
            q, k, v, jnp.int32(50), block=16, dense_max=0, use_kernel=True
        )
        np.testing.assert_allclose(np.asarray(kern), np.asarray(walk), atol=2e-5)

    def test_non_tileable_length_falls_back_to_walk(self):
        # L=20: every power-of-two-halved block either fails L % b or b % 8
        # — the dispatcher must fall back, not crash.
        assert decode_block_fits(1024, 20) is None
        q, k, v = _bufs(L=20, idx=13)
        out = decode_attention(
            q, k, v, jnp.int32(13), block=16, dense_max=0, use_kernel=True
        )
        ref = decode_attention(
            q, k, v, jnp.int32(13), block=16, dense_max=0, use_kernel=False
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("window", [8, 16, 40, 100])
    def test_windowed_kernel_matches_windowed_walk(self, window):
        # Sliding-window decode through the kernel: the two-sided clamp
        # (pre-window AND post-prefix steps collapse onto boundary blocks)
        # must reproduce the windowed walk at every window size — inside a
        # block, block-aligned, spanning blocks, and >= fill (plain prefix).
        q, k, v = _bufs(idx=50)
        out = decode_attention(
            q, k, v, jnp.int32(50), block=16, dense_max=0, window=window,
            use_kernel=True,
        )
        ref = decode_attention(
            q, k, v, jnp.int32(50), block=16, dense_max=0, window=window,
            use_kernel=False,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_windowed_kernel_skips_prewindow_blocks(self):
        """Poison blocks wholly before the window AND wholly after the
        prefix: the clamped index map must read neither."""
        q, k, v = _bufs(B=1, L=128, idx=79)  # window 16 -> rows 64..79
        k = np.array(k); v = np.array(v)
        k[:, :48] = np.nan; v[:, :48] = np.nan   # pre-window blocks (16-row)
        k[:, 96:] = np.nan; v[:, 96:] = np.nan   # past the boundary block
        out = flash_decode(
            q, jnp.asarray(k), jnp.asarray(v), jnp.int32(79), block=16,
            interpret=True, window=16,
        )
        assert np.all(np.isfinite(np.asarray(out)))

    def test_cpu_auto_keeps_walk(self):
        # use_kernel=None on CPU: the walk (fast XLA) — the interpreter
        # would be a silent order-of-magnitude regression for CPU serving.
        q, k, v = _bufs(idx=50)
        out = decode_attention(q, k, v, jnp.int32(50), block=16, dense_max=0)
        ref = decode_attention(
            q, k, v, jnp.int32(50), block=16, dense_max=0, use_kernel=False
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_block_fits():
    assert decode_block_fits(1024, 2048) == 1024
    assert decode_block_fits(1024, 1536) == 512
    assert decode_block_fits(16, 64) == 16
    assert decode_block_fits(1024, 20) is None
    # 1048 is only tileable by a degenerate 8-row block — a 131-step
    # near-scalar grid must fall back to the walk, not run (review r5).
    assert decode_block_fits(1024, 1048) is None
