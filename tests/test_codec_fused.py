"""Fused single-launch codec: bit-equivalence with the legacy per-tensor
loop across modes x dtypes x delta layouts, awkward leaves (block padding,
scalars, empties), batch-group encode/decode, self-describing payloads,
and the mode-aware accounting fixes.  No optional test deps -- this module
always runs (tests/test_compression.py holds the hypothesis-gated
property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import (ActivationCodec, spatial_delta_axis)


def _roundtrip(codec, tree):
    p = codec.compress(tree)
    return p, codec.decompress(p)


def _tree(dtype):
    """Multi-leaf pytree exercising nonzero block padding, a scalar and an
    empty leaf alongside feature-map-like tensors."""
    ka, kb, kc = jax.random.split(jax.random.PRNGKey(3), 3)
    return {
        "a": (jax.random.normal(ka, (2, 13, 7, 24)) * 5).astype(dtype),
        "b": (jax.random.normal(kb, (311,)) * 0.3).astype(dtype),
        "scalar": jnp.asarray(2.75, dtype),
        "empty": jnp.zeros((0, 4), dtype),
        "c": jax.random.normal(kc, (1, 6, 6, 3)).astype(dtype),
    }


@pytest.mark.parametrize("mode", ["int8", "int8_zlib", "int8_delta_zlib"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_roundtrip_matches_legacy_bit_exact(mode, dtype):
    """The fused single-launch encoder and the legacy per-tensor loop must
    decode to IDENTICAL tensors for every int8-family mode and dtype."""
    tree = _tree(dtype)
    legacy = ActivationCodec(mode=mode, quant_block=256, fused=False)
    fused = ActivationCodec(mode=mode, quant_block=256)
    pl_, out_l = _roundtrip(legacy, tree)
    pf, out_f = _roundtrip(fused, tree)
    assert not pl_.fused and pf.fused
    assert pl_.raw_bytes == pf.raw_bytes
    for key in tree:
        a, b = np.asarray(out_l[key]), np.asarray(out_f[key])
        assert a.dtype == b.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("layout", ["spatial", "block"])
def test_delta_layouts_roundtrip_identically(layout):
    """Both fused delta geometries are lossless on the same quant grid --
    and the spatial layout must actually beat plain int8_zlib on smooth
    feature maps (the reason the delta mode exists)."""
    g = np.linspace(0, 4, 56)
    x = {"x": jnp.asarray(np.sin(g)[None, :, None, None]
                          + np.cos(g)[None, None, :, None]
                          + 0.1 * np.random.default_rng(0).normal(
                              size=(1, 56, 56, 24)), jnp.float32)}
    base = ActivationCodec(mode="int8_zlib", quant_block=1024)
    delta = ActivationCodec(mode="int8_delta_zlib", quant_block=1024,
                            delta_layout=layout)
    pb, ob = _roundtrip(base, x)
    pd, od = _roundtrip(delta, x)
    np.testing.assert_array_equal(np.asarray(ob["x"]), np.asarray(od["x"]))
    if layout == "spatial":
        assert pd.compressed_bytes < pb.compressed_bytes
        assert pd.meta[0].delta_axis == spatial_delta_axis(x["x"].shape) == 1
    else:
        assert pd.meta[0].delta_axis is None   # block layout: no per-leaf axis


def test_fused_payload_is_self_describing():
    """A fused payload decodes correctly through a receiver constructed
    with a different default mode AND with fused=False (the payload's own
    layout wins, like the mode field)."""
    x = {"x": jax.random.normal(jax.random.PRNGKey(4), (1, 10, 10, 8))}
    p = ActivationCodec(mode="int8_delta_zlib").compress(x)
    want = np.asarray(
        ActivationCodec(mode="int8_delta_zlib").decompress(p)["x"])
    for receiver in (ActivationCodec(mode="int8_zlib"),
                     ActivationCodec(mode="raw", fused=False),
                     ActivationCodec(mode="int8_delta_zlib",
                                     delta_layout="block")):
        np.testing.assert_array_equal(
            np.asarray(receiver.decompress(p)["x"]), want)


def test_fused_empty_tree():
    codec = ActivationCodec()
    p = codec.compress({})
    assert codec.decompress(p) == {}
    assert p.raw_bytes == 0


def test_decompress_group_rejects_mixed_settings():
    x = {"x": jax.random.normal(jax.random.PRNGKey(12), (1, 8, 8, 4))}
    a = ActivationCodec(mode="int8_zlib", quant_block=256).compress(x)
    b = ActivationCodec(mode="int8_delta_zlib", quant_block=256).compress(x)
    c = ActivationCodec(mode="int8_zlib", quant_block=1024).compress(x)
    codec = ActivationCodec(quant_block=256)
    for bad in ([a, b], [a, c]):
        with pytest.raises(ValueError, match="mixes codec settings"):
            codec.decompress_group(bad)


def test_non_lane_aligned_block_raises_clearly():
    """Both encoders tile the stream into 128-lane rows (the legacy quant
    kernel asserts this deep inside pallas); the codec surfaces the
    constraint as a readable error instead of a reshape crash."""
    x = {"x": jax.random.normal(jax.random.PRNGKey(11), (1, 9, 9, 7))}
    with pytest.raises(ValueError, match="multiple of 128"):
        ActivationCodec(mode="int8_delta_zlib", quant_block=1000).compress(x)


def test_legacy_handles_scalar_and_empty_leaves():
    """The per-tensor loop (and the quant kernels underneath) must not
    choke on degenerate leaves either."""
    tree = [jnp.asarray(1.5), jnp.zeros((0, 3)), jnp.ones((5,))]
    codec = ActivationCodec(fused=False)
    _, out = _roundtrip(codec, tree)
    assert np.asarray(out[0]).shape == ()
    assert out[1].shape == (0, 3)


def test_compress_group_bit_identical_to_per_tree():
    """Group encode = one launch over every tree's leaves, but per-tree
    blobs/scales must be BYTE-identical to per-tree compress (the uplink
    and the receiver cannot tell the difference)."""
    rng = np.random.default_rng(5)
    trees = [{"x": jnp.asarray(rng.normal(size=(1, 9, 9, 16)) * (i + 1),
                               jnp.float32),
              "y": jnp.asarray(rng.normal(size=(77,)), jnp.float32)}
             for i in range(4)]
    for mode in ("int8_zlib", "int8_delta_zlib"):
        codec = ActivationCodec(mode=mode, quant_block=256)
        group = codec.compress_group(trees)
        solo = [codec.compress(t) for t in trees]
        for g, s in zip(group, solo):
            assert g.blobs == s.blobs
            np.testing.assert_array_equal(g.scales[0], s.scales[0])
            assert g.compressed_bytes == s.compressed_bytes
            assert [m.block_start for m in g.meta] == \
                [m.block_start for m in s.meta]
        outs = codec.decompress_group(group)
        for og, s in zip(outs, solo):
            os_ = codec.decompress(s)
            for lg, ls in zip(jax.tree.leaves(og), jax.tree.leaves(os_)):
                np.testing.assert_array_equal(np.asarray(lg), np.asarray(ls))


# -- accounting fixes ----------------------------------------------------------

def test_estimate_bytes_zlib_mode_uses_raw_float_bytes():
    """mode='zlib' compresses raw floats; its estimate must scale the RAW
    bytes, not the int8-quantized size."""
    specs = [((64, 64, 16), "float32")]
    raw = 64 * 64 * 16 * 4
    est = ActivationCodec(mode="zlib").estimate_bytes(specs)
    assert raw / 2 < est <= raw          # floats barely compress
    assert est == int(raw * ActivationCodec.DEFAULT_RATIOS["zlib"])
    # measured feedback applies to the same base
    assert ActivationCodec(mode="zlib").estimate_bytes(
        specs, measured_ratio=0.5) == raw // 2


def test_estimate_bytes_delta_mode_has_own_default_ratio():
    specs = [((64, 64, 16), "float32")]
    base = ActivationCodec(mode="int8_zlib").estimate_bytes(specs)
    delta = ActivationCodec(mode="int8_delta_zlib").estimate_bytes(specs)
    assert delta < base                  # the filter buys compressibility
    n = 64 * 64 * 16
    int8 = n + 4 * (n // 8192 + 1)
    assert delta == int(int8 * ActivationCodec.DEFAULT_RATIOS["int8_delta_zlib"])


def test_legacy_delta_axis_recorded_in_meta():
    """The delta filter's axis choice is made once at encode time and
    shipped in TensorMeta -- the decoder honors the recorded axis instead
    of re-deriving the heuristic."""
    codec = ActivationCodec(mode="int8_delta_zlib", fused=False)
    thin = codec.compress([jnp.ones((1, 8, 8, 4))])     # shape[0] < 4
    wide = codec.compress([jnp.ones((8, 8, 8, 4))])
    assert thin.meta[0].delta_axis == 1
    assert wide.meta[0].delta_axis == 0
    # a payload predating the field (delta_axis=None) still decodes via
    # the historical heuristic fallback
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 12, 12, 8))
    p = codec.compress([x])
    want = np.asarray(codec.decompress(p)[0])
    p.meta[0].delta_axis = None
    np.testing.assert_array_equal(np.asarray(codec.decompress(p)[0]), want)
