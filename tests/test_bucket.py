"""Bucketed wire path: layout round-trips, fused-kernel parity vs the
pure-jnp oracles (interpret=True), and device-free bit-for-bit equality of
the bucketed and per-leaf wire exchanges."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # optional dep: fall back to
    from tests._hypothesis_compat import (  # deterministic shim
        given, settings, strategies as st)

from repro.core import bucket
from repro.kernels import ops as kops
from repro.kernels import quantize as qk
from repro.kernels import ref as kref
from repro.optim.wire import WireExchange

SHAPE_SETS = [
    [(1, 64), (1, 4, 256), (1, 300)],                 # ragged last dim
    [(1, 8, 256), (1, 2, 2, 128), (1, 5), (1, 16)],   # mixed widths
    [(1, 1)],                                         # degenerate scalarish
    [(1, 257), (1, 3, 511)],                          # odd widths (padded)
]


def _leaves(shapes, key, dtype=jnp.float32):
    ks = jax.random.split(key, len(shapes))
    return [(jax.random.normal(k, s) * 2).astype(dtype)
            for k, s in zip(ks, shapes)]


class TestLayout:
    @pytest.mark.parametrize("shapes", SHAPE_SETS)
    def test_row_mapping_round_trip(self, shapes):
        """Every leaf is recovered exactly from its group row table."""
        layout = bucket.compute_layout(shapes, [jnp.float32] * len(shapes),
                                       bits=2)
        leaves = _leaves(shapes, jax.random.key(0))
        for sl, leaf in zip(layout.slots, leaves):
            rows = kops.blockwise_lastdim(leaf, block=sl.block).reshape(
                -1, sl.block)
            assert rows.shape[0] == sl.rows
            back = bucket.rows_to_leaf(sl, rows)
            np.testing.assert_array_equal(np.asarray(back),
                                          np.asarray(leaf))

    @pytest.mark.parametrize("shapes", SHAPE_SETS)
    def test_offsets_partition_the_buffers(self, shapes):
        """Group segments tile the two wire buffers exactly: contiguous,
        non-overlapping, and summing to the buffer sizes."""
        layout = bucket.compute_layout(shapes, [jnp.float32] * len(shapes),
                                       bits=2)
        c_off = s_off = 0
        for g in layout.groups:
            assert g.codes_offset == c_off
            assert g.scales_offset == s_off
            c_off += g.rows * g.packed_width
            s_off += g.rows * layout.scale_bytes
        assert c_off == layout.codes_bytes
        assert s_off == layout.scales_bytes
        # every leaf belongs to exactly one group, rows partition each group
        seen = sorted(i for g in layout.groups for i in g.leaf_indices)
        assert seen == list(range(len(shapes)))
        for g in layout.groups:
            offs = sorted((layout.slots[i].row_offset, layout.slots[i].rows)
                          for i in g.leaf_indices)
            pos = 0
            for (r0, n) in offs:
                assert r0 == pos
                pos += n
            assert pos == g.rows

    def test_no_padded_block_ships(self):
        """A leaf with an even last dim below the block width quantizes at
        its own width: wire bytes beat the naive padded-block layout."""
        layout = bucket.compute_layout([(1, 64)], [jnp.float32], bits=2)
        assert layout.slots[0].block == 64
        assert layout.codes_bytes == 64 // 2    # nibble-packed, no padding
        padded = bucket.compute_layout([(1, 64)], [jnp.float32], bits=2,
                                       block_for=lambda s: 256)
        assert padded.codes_bytes == 256 // 2

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 5), st.integers(1, 300)),
                    min_size=1, max_size=6),
           st.sampled_from([1, 2, 3, 4]))
    def test_wire_round_trip_property(self, dims, bits):
        """pack_to_wire -> mix_from_wire with identity self-weight recovers
        exactly the per-leaf quantize/dequantize of every leaf."""
        shapes = [(1, a, b) for a, b in dims]
        leaves = _leaves(shapes, jax.random.key(7))
        layout = bucket.compute_layout(shapes, [l.dtype for l in leaves],
                                       bits=bits)
        keys = jax.random.split(jax.random.key(3), len(leaves))
        cw, sw = bucket.pack_to_wire(layout, leaves, keys)
        assert cw.shape == (layout.codes_bytes,) and cw.dtype == jnp.uint8
        assert sw.shape == (layout.scales_bytes,) and sw.dtype == jnp.uint8
        _, qs = bucket.mix_from_wire(layout, [(cw, sw)],
                                     jnp.ones((1, 1), jnp.float32))
        for leaf, k, sl, q in zip(leaves, keys, layout.slots, qs):
            codes, scales = kops.qinf_quantize_lastdim(
                leaf, k, bits=bits, block=sl.block)
            want = kops.qinf_dequantize_lastdim(
                codes, scales.astype(jnp.float32), leaf.shape, leaf.dtype,
                block=sl.block)
            np.testing.assert_array_equal(np.asarray(q), np.asarray(want))


class TestFusedKernels:
    @pytest.mark.parametrize("bits", [1, 2, 3, 4, 7])
    @pytest.mark.parametrize("rows", [8, 24])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_quantize_pack_matches_ref(self, bits, rows, dtype):
        x = (jax.random.normal(jax.random.key(0), (rows, 256)) * 3).astype(
            dtype)
        u = jax.random.uniform(jax.random.key(1), (rows, 256), jnp.float32)
        pk, sk = qk.qinf_quantize_pack_blocks(x.astype(jnp.float32), u,
                                              bits=bits, block=256,
                                              interpret=True)
        pr, sr = kref.qinf_quantize_pack_blocks_ref(x, u, bits)
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
        # the packed bytes decode to the plain quantizer's codes
        ck, _ = qk.qinf_quantize_blocks(x.astype(jnp.float32), u, bits=bits,
                                        block=256, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(kref.unpack_codes_halves_ref(pk, bits)),
            np.asarray(ck))

    @pytest.mark.parametrize("bits", [1, 2, 3, 4, 7])
    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_unpack_dequant_mix_matches_ref(self, bits, out_dtype):
        S, T, R, B = 3, 2, 16, 256
        ks = jax.random.split(jax.random.key(2), S)
        packed, scales = [], []
        for k in ks:
            x = jax.random.normal(k, (R, B)) * 2
            u = jax.random.uniform(jax.random.fold_in(k, 1), (R, B))
            p, s = kref.qinf_quantize_pack_blocks_ref(x, u, bits)
            packed.append(p)
            scales.append(s)
        packed = jnp.stack(packed)
        scales = jnp.stack(scales)
        w = jax.random.normal(jax.random.key(3), (T, S)).astype(jnp.float32)
        mk, qk_ = qk.qinf_unpack_dequant_mix_blocks(
            packed, scales, w, bits=bits, block=B, out_dtype=out_dtype,
            interpret=True)
        # the oracle runs compiled, as it does inside the trainer's step
        mr, qr = jax.jit(functools.partial(
            kref.qinf_unpack_dequant_mix_blocks_ref, bits=bits,
            out_dtype=out_dtype))(packed, scales, w)
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
        np.testing.assert_array_equal(np.asarray(qk_), np.asarray(qr))

    @pytest.mark.parametrize("rows", [1, 7, 13])
    def test_ops_wrapper_pads_and_slices(self, rows):
        """The ops dispatch pads ragged row counts for the kernel and
        slices back — pallas and ref agree for any R."""
        x = jax.random.normal(jax.random.key(0), (rows, 128))
        u = jax.random.uniform(jax.random.key(1), (rows, 128))
        for use_pallas in (False, True):
            p, s = kops.qinf_quantize_pack(x, u, bits=2, block=128,
                                           use_pallas=use_pallas)
            assert p.shape == (rows, 64) and s.shape == (rows, 1)
        pr, _ = kops.qinf_quantize_pack(x, u, bits=2, block=128,
                                        use_pallas=False)
        pp_, _ = kops.qinf_quantize_pack(x, u, bits=2, block=128,
                                         use_pallas=True)
        np.testing.assert_array_equal(np.asarray(pr), np.asarray(pp_))


class TestWireExchangeParity:
    """Device-free bit-for-bit parity: with a self-loop ppermute stub the
    full exchange (quantize -> wire -> mix) must agree exactly between
    modes, including the T > 1 weight tables and bf16 leaves.  Both modes
    run under jit, as they do inside the trainer's shard_map."""

    @staticmethod
    def _exchanges(wx, diffs, keys, wmat, hop_pairs):
        pp = lambda x, pairs: x          # self-loop: "receive" own payload
        run = jax.jit(lambda mode, d, w: getattr(wx, mode)(
            d, keys, w, hop_pairs, pp), static_argnums=0)
        return run("bucketed", diffs, wmat), run("per_leaf", diffs, wmat)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T", [1, 3])
    @pytest.mark.parametrize("bits", [2, 4])
    def test_bucketed_equals_per_leaf(self, dtype, T, bits):
        shapes = [(1, 64), (1, 4, 256), (1, 304), (1, 8, 104), (1, 5)]
        diffs = _leaves(shapes, jax.random.key(0), dtype)
        keys = list(jax.random.split(jax.random.key(1), len(shapes)))
        hops = 2
        wmat = jax.random.normal(jax.random.key(2),
                                 (1 + hops, T)).astype(jnp.float32)
        hop_pairs = [[(i, i) for i in range(4)] for _ in range(hops)]
        (wq_b, qs_b), (wq_p, qs_p) = self._exchanges(
            WireExchange(bits=bits), diffs, keys, wmat, hop_pairs)
        for a, b in zip(wq_b, wq_p):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(qs_b, qs_p):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_odd_widths_agree_to_the_ulp(self):
        """Leaves whose last dim is not lane-aligned (e.g. 300, 100) mix
        in loops with unaligned vector tails, where XLA's CPU backend
        contracts other products into FMAs than in the bucketed rows; the
        mix's partial products are exact, so the modes agree to the bit."""
        shapes = [(1, 300), (1, 8, 100), (1, 7, 13)]
        diffs = _leaves(shapes, jax.random.key(0))
        keys = list(jax.random.split(jax.random.key(1), len(shapes)))
        wmat = jax.random.normal(jax.random.key(2),
                                 (3, 3)).astype(jnp.float32)
        hop_pairs = [[(i, i) for i in range(4)] for _ in range(2)]
        (wq_b, qs_b), (wq_p, qs_p) = self._exchanges(
            WireExchange(bits=2), diffs, keys, wmat, hop_pairs)
        for a, b in zip(qs_b + wq_b, qs_p + wq_p):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_scales_bf16_parity(self):
        shapes = [(1, 256), (1, 3, 64)]
        diffs = _leaves(shapes, jax.random.key(0))
        keys = list(jax.random.split(jax.random.key(1), len(shapes)))
        wmat = jnp.asarray([[0.4], [0.3], [0.3]], jnp.float32)
        (wq_b, qs_b), (wq_p, qs_p) = self._exchanges(
            WireExchange(bits=2, scales_bf16=True), diffs, keys, wmat,
            [[(0, 0)]] * 2)
        for a, b in zip(wq_b + qs_b, wq_p + qs_p):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wire_bits_match_accounting(self):
        """layout.wire_bits == the per-leaf qinf_wire_bits sum (the number
        asserted byte-exact against the HLO in test_dryrun_small)."""
        from repro.netsim.metrics import qinf_wire_bits
        shapes = [(1, 64), (1, 4, 256), (1, 300), (1, 5)]
        wx = WireExchange(bits=2)
        layout = wx.layout(shapes, [jnp.float32] * len(shapes))
        per_leaf = sum(
            qinf_wire_bits(s, bits=2, block=layout.slots[i].block)
            for i, s in enumerate(shapes))
        assert layout.wire_bits == per_leaf
