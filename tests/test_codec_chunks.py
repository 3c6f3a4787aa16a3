"""The fused codec's entropy stage: each payload's int8 stream deflated as
independent zlib streams of ``CHUNK_BLOCKS`` quant blocks, every chunk of
a pass on one host thread pool.  Chunk boundaries depend on the payload's
size alone, so the blobs are the same whatever the pool's width; decoded
tensors are bit-identical to the one-stream layout's."""
import sys
import threading
import time
import types
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as CC
from repro.core import telemetry as T
from repro.core.compression import ActivationCodec, CompressedPayload

BLOCK = 256                                  # a chunk is 32 * 256 bytes
CHUNK = CC.CHUNK_BLOCKS * BLOCK


def _tree(n, seed=0):
    """One smooth-ish feature map of ``n`` elements plus a short leaf."""
    rng = np.random.default_rng(seed)
    x = np.cumsum(rng.normal(size=n)).astype(np.float32) * 0.1
    return {"x": jnp.asarray(x), "y": jnp.asarray(rng.normal(size=(37,)),
                                                  jnp.float32)}


def _n_blocks(tree):
    return sum(-(-x.size // BLOCK) for x in jax.tree.leaves(tree))


def _stream(p):
    return b"".join(zlib.decompress(b) for b in p.blobs)


def _one_blob(p, level=1):
    """The same payload with its stream as a single zlib stream: the
    layout before chunking."""
    return CompressedPayload([zlib.compress(_stream(p), level)], p.scales,
                             p.meta, p.raw_bytes, p.treedef, mode=p.mode,
                             fused=True, delta_layout=p.delta_layout)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) > 0
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# the short leaf adds one block; ``n`` sets the rest
SIZES = {"below_one_chunk": 20 * BLOCK - 7,
         "exact_chunks": 3 * CHUNK - BLOCK,
         "chunks_and_partial": 3 * CHUNK + 5 * BLOCK + 11}


@pytest.mark.parametrize("mode", ["int8_zlib", "int8_delta_zlib"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_roundtrip_is_bit_identical_at_every_chunk_count(size, mode):
    tree = _tree(SIZES[size])
    codec = ActivationCodec(mode=mode, quant_block=BLOCK)
    p = codec.compress(tree)
    nb = _n_blocks(tree)
    assert len(p.blobs) == -(-nb // CC.CHUNK_BLOCKS)
    assert len(p.blobs) == {"below_one_chunk": 1, "exact_chunks": 3,
                            "chunks_and_partial": 4}[size]
    # every chunk but the last is a whole CHUNK_BLOCKS blocks of the stream
    sizes = [len(zlib.decompress(b)) for b in p.blobs]
    assert sizes[:-1] == [CHUNK] * (len(sizes) - 1)
    assert sum(sizes) == nb * BLOCK
    got = codec.decompress(p)
    _assert_trees_equal(got, codec.decompress(_one_blob(p)))
    legacy = ActivationCodec(mode=mode, quant_block=BLOCK, fused=False)
    _assert_trees_equal(got, legacy.decompress(legacy.compress(tree)))


def test_blobs_do_not_depend_on_the_pool_width(monkeypatch):
    tree = _tree(5 * CHUNK + 3 * BLOCK)
    codec = ActivationCodec(quant_block=BLOCK)
    wide = codec.compress(tree)
    one = ThreadPoolExecutor(1)
    try:
        monkeypatch.setattr(CC, "_pool", lambda: (one, 1))
        narrow = codec.compress(tree)
        rec = T.HostRecorder()
        with T.recording(rec):
            codec.decompress(narrow)
    finally:
        one.shutdown()
    assert len(wide.blobs) == 6
    assert narrow.blobs == wide.blobs
    (zl,) = [s for s in rec.spans if s.name == "codec.zlib"]
    assert zl.attrs["threads"] == 1 and zl.attrs["chunks"] == 6
    # and each blob is plain zlib of its fixed range of the stream
    stream = _stream(wide)
    assert wide.blobs == [zlib.compress(stream[i:i + CHUNK], codec.level)
                          for i in range(0, len(stream), CHUNK)]


def test_a_one_chunk_pass_runs_inline(monkeypatch):
    def no_pool():
        raise AssertionError("a single chunk went to the pool")
    monkeypatch.setattr(CC, "_pool", no_pool)
    codec = ActivationCodec(quant_block=BLOCK)
    tree = _tree(10 * BLOCK)
    rec = T.HostRecorder()
    with T.recording(rec):
        p = codec.compress(tree)
        codec.decompress(p)
    assert len(p.blobs) == 1
    zl = [s for s in rec.spans if s.name == "codec.zlib"]
    assert [(s.parent, s.attrs["chunks"], s.attrs["threads"]) for s in zl] \
        == [("codec.encode", 1, 1), ("codec.decode", 1, 1)]
    assert all(s.thread == threading.get_ident() for s in rec.spans)


@pytest.mark.parametrize("mode", ["int8_zlib", "int8_delta_zlib"])
def test_compress_group_blobs_equal_per_tree_compress(mode):
    trees = [_tree(n, seed=i) for i, n in
             enumerate([2 * CHUNK + 9, 10 * BLOCK, 4 * CHUNK - BLOCK])]
    codec = ActivationCodec(mode=mode, quant_block=BLOCK)
    rec = T.HostRecorder()
    with T.recording(rec):
        group = codec.compress_group(trees)
        outs = codec.decompress_group(group)
    solo = [codec.compress(t) for t in trees]
    assert [len(g.blobs) for g in group] == [3, 1, 4]
    for g, s in zip(group, solo):
        assert g.blobs == s.blobs
        np.testing.assert_array_equal(g.scales[0], s.scales[0])
        assert g.compressed_bytes == s.compressed_bytes
    for o, s in zip(outs, solo):
        _assert_trees_equal(o, codec.decompress(s))
    zl = [s for s in rec.spans if s.name == "codec.zlib"]
    assert [(s.parent, s.attrs["payloads"], s.attrs["chunks"]) for s in zl] \
        == [("codec.encode", 3, 8), ("codec.decode", 3, 8)]
    assert zl[0].attrs["bytes_out"] == zl[1].attrs["bytes_in"] \
        == sum(len(b) for g in group for b in g.blobs)


def test_a_single_blob_fused_payload_still_decodes():
    """A payload in the one-stream layout (written before chunking) decodes
    to the same tensors, alone and in a group with chunked payloads."""
    codec = ActivationCodec(quant_block=BLOCK)
    trees = [_tree(3 * CHUNK + 100, seed=1), _tree(2 * CHUNK, seed=2)]
    chunked = [codec.compress(t) for t in trees]
    old = _one_blob(chunked[0])
    assert len(old.blobs) == 1 and len(chunked[0].blobs) == 4
    want = codec.decompress(chunked[0])
    _assert_trees_equal(codec.decompress(old), want)
    mixed = codec.decompress_group([old, chunked[1]])
    _assert_trees_equal(mixed[0], want)
    _assert_trees_equal(mixed[1], codec.decompress(chunked[1]))


def test_chunked_bytes_stay_within_a_thousandth_of_one_stream():
    """At the configurations' quant block (8192, 256 KiB chunks) a Gaussian
    feature map's chunked payload costs what one zlib stream does, to
    within 0.1%: each chunk's fresh Huffman tables are small beside it."""
    rng = np.random.default_rng(7)
    codec = ActivationCodec()
    n_blocks = 5 * CC.CHUNK_BLOCKS + 7
    x = jnp.asarray(rng.normal(size=(n_blocks * codec.quant_block,)),
                    jnp.float32)
    p = codec.compress({"x": x})
    assert len(p.blobs) == 6
    one = len(zlib.compress(_stream(p), codec.level)) + p.scales[0].nbytes
    assert abs(p.compressed_bytes - one) <= 1e-3 * one


def test_encode_s_charges_each_payload_its_own_chunks(monkeypatch):
    """A payload's ``encode_s`` is its share of the pass outside zlib plus
    its own chunks' deflate seconds, timed around calls that reach zlib
    through the codec module's name (so a wrap of that name sees them)."""
    calls = []

    def slow_compress(buf, level):
        calls.append(len(buf))
        time.sleep(0.02)
        return zlib.compress(buf, level)

    proxy = types.ModuleType("zlib")
    proxy.__dict__.update(zlib.__dict__)
    proxy.compress = slow_compress
    monkeypatch.setattr(CC, "zlib", proxy)
    trees = [_tree(3 * CHUNK, seed=3), _tree(10 * BLOCK, seed=4)]
    codec = ActivationCodec(quant_block=BLOCK)
    rec = T.HostRecorder()
    with T.recording(rec):
        group = codec.compress_group(trees)
    assert len(calls) == sum(len(g.blobs) for g in group) == 5
    enc = next(s for s in rec.spans if s.name == "codec.encode")
    zl = next(s for s in rec.spans if s.name == "codec.zlib")
    share = (enc.seconds - zl.seconds) / len(group)
    own = [g.encode_s - share for g in group]
    assert own[0] >= 4 * 0.02 and own[1] >= 0.02      # 4 chunks and 1
    assert sum(own) <= zl.seconds * zl.attrs["threads"]


def test_concurrent_first_passes_share_one_pool(monkeypatch):
    """Many encoders racing on a fresh process make one pool, and every
    one gets the same bytes."""
    monkeypatch.setattr(CC, "_POOL", None)
    made = []
    real = ThreadPoolExecutor

    def counting(*a, **k):
        made.append(1)
        return real(*a, **k)

    monkeypatch.setattr(CC, "ThreadPoolExecutor", counting)
    codec = ActivationCodec(quant_block=BLOCK)
    tree = _tree(3 * CHUNK + 17)
    x = np.asarray(tree["x"])
    want = codec.compress(tree).blobs
    monkeypatch.setattr(CC, "_POOL", None)
    made.clear()
    got, errors = [], []

    def work():
        try:
            got.append(codec.compress({"x": jnp.asarray(x),
                                       "y": tree["y"]}).blobs)
        except Exception as e:          # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
        CC._POOL[0].shutdown()
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(made) == 1
    assert got == [want] * 12
