"""Activation compression pipeline (paper §IV-C).

Two stages, exactly as the paper:
  (1) FP32 -> INT8 per-block absmax quantization.  Device-side; runs the
      Pallas TPU kernels (bitwise-identical jnp path off-TPU, ops.py).
  (2) zlib entropy coding of the int8 bytes.  Host-side: TPUs have no
      entropy-coder unit (DESIGN.md §2) -- the paper likewise runs zlib on
      the UE CPU.  Each payload's stream is cut into fixed ranges of
      ``CHUNK_BLOCKS`` quant blocks, each its own zlib stream, so one
      encode or decode pass deflates or inflates every chunk of every
      payload at once on a shared host thread pool (zlib releases the
      GIL).  Where the cuts fall depends on the payload's size alone.

The codec operates on arbitrary pytrees (the Swin boundary payload is a
dict of feature maps; LM split payloads carry the residual stream plus any
SSM/KV state that moves with the split point).

Two encoders produce interchangeable results:

  * the FUSED path (default): every leaf is packed into one flat
    block-aligned stream and a single Pallas launch (kernels/codec.py)
    computes scales + int8 quant (+ the mod-256 delta filter: in-register
    per grid step with ``delta_layout='block'``, or the legacy-equivalent
    per-leaf spatial delta fused into the same executable as an integer
    epilogue with the default ``'spatial'``); one device->host transfer
    and one deflate pass (its fixed-size chunks) covers the whole payload.
    Jitted encode/decode closures are trace-cached per (mode, quant
    block); jax.jit keys the per-leaf-shape-signature traces underneath,
    so nothing retraces per frame.  ``compress_group`` extends the same
    single launch across many same-mode payloads (the cell's per-slot
    batch group) while emitting per-payload blobs that are byte-identical
    to what per-payload ``compress`` would produce.
  * the LEGACY per-tensor loop (``fused=False``): one quant launch, one
    transfer and one zlib call per leaf, with the delta filter on the
    host.  Kept as the compatibility decoder for ``mode=None`` payloads
    and as the baseline in benchmarks/bench_compression.py.

The paths may lay out delta streams differently (the host image-row
delta, its fused 'spatial' equivalent, or the kernel's block-local
'block' variant), but every layout is exactly invertible on the same
quantized grid, so *decompressed tensors are bit-identical* whichever
encoder produced the payload (DESIGN.md §5).
"""
from __future__ import annotations

import functools
import os
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import host_span
from repro.kernels import ops

_INT8_MODES = ("int8", "int8_zlib", "int8_delta_zlib")

# quant blocks per independent zlib stream of a fused payload: 256 KiB of
# int8 at the configurations' quant_block of 8192.  Fixed, so the bytes on
# the wire are the same on any host.
CHUNK_BLOCKS = 32


def spatial_delta_axis(shape: Tuple[int, ...]) -> Optional[int]:
    """The delta filter's axis choice, made ONCE at encode time and recorded
    in ``TensorMeta.delta_axis`` so encoder and decoder can never disagree:
    the first spatial axis (skipping a small leading batch dim).  None for
    tensors the filter does not apply to."""
    if len(shape) < 3 or int(np.prod(shape)) == 0:
        return None
    return 1 if shape[0] < 4 else 0


def _delta_stride(shape: Tuple[int, ...], axis: int) -> int:
    return int(np.prod(shape[axis + 1:])) if len(shape) > axis + 1 else 1


@dataclass
class TensorMeta:
    shape: Tuple[int, ...]
    dtype: str
    n: int                    # valid element count (pre-padding)
    n_blocks: int
    block: int
    # delta filter: the spatial axis chosen at encode time (see
    # spatial_delta_axis); None = leaf not filtered (or a pre-field legacy
    # payload -- the legacy decoder falls back to the historical heuristic).
    delta_axis: Optional[int] = None
    # fused stream: index of this leaf's first quant block in the packed
    # stream (segment offset = block_start * block elements/bytes).
    block_start: int = 0


@dataclass
class CompressedPayload:
    """What actually crosses the uplink.

    ``mode`` records the codec mode the payload was produced with, so the
    receiver decodes it correctly even if its own codec was constructed
    with a different default (None = legacy payload, decoder's mode wins).
    ``encode_s`` is what one UE's own CPU spent on this payload: its
    share of the ``codec.encode`` span's wall time outside ``codec.zlib``
    (the span over the frames it encoded), plus the seconds its own
    chunks took to deflate, on whatever threads ran them.  It is not part
    of the payload's value.
    ``fused`` marks the single-stream layout: ``scales`` holds ONE entry
    covering every leaf, ``meta[i].block_start`` locates leaf i's segment
    inside the stream, and ``blobs`` is the stream's consecutive ranges of
    ``CHUNK_BLOCKS`` quant blocks (the last may be short), each an
    independent zlib stream, in order: the stream is their inflated bytes
    concatenated, so a payload of one blob decodes alike.  Mode 'int8'
    ships the stream as one raw blob.  ``delta_layout`` records which
    delta geometry a fused delta stream was written with ('spatial' |
    'block'), so any receiver inverts it correctly."""
    blobs: List[bytes]                 # zlib(int8 blocks); one per tensor,
                                       # or the packed stream's chunks (fused)
    scales: List[np.ndarray]           # f32 per-block scales (shipped raw)
    meta: List[TensorMeta]
    raw_bytes: int                     # payload size before compression
    treedef: Any = None
    mode: Optional[str] = None
    fused: bool = False
    delta_layout: Optional[str] = None
    encode_s: float = field(default=0.0, compare=False)

    @property
    def compressed_bytes(self) -> int:
        return (sum(len(b) for b in self.blobs)
                + sum(s.nbytes for s in self.scales))

    @property
    def ratio(self) -> float:
        return self.compressed_bytes / max(self.raw_bytes, 1)


# ---------------------------------------------------------------------------
# fused-path trace cache
# ---------------------------------------------------------------------------
#
# One jitted closure per (quant block, delta layout) for encode and per
# (segment layout, quant block, delta layout) for decode.  jax.jit's own
# cache keys the traces on the leaf-shape signature, so a frame with
# payload shapes seen before costs zero retracing.
#
# Two delta layouts, both single-launch:
#   'spatial' (default): the quant kernel emits the int8 grid and a fused
#       integer epilogue (same jitted executable) applies the legacy-
#       equivalent per-leaf spatial delta -- stride = one row along the
#       recorded delta_axis -- before the stream leaves the device.  Best
#       compression (feature maps are spatially smooth).
#   'block': the kernel's fully in-register variant -- the delta runs per
#       grid step inside the Pallas kernel (stride = one 128-lane sublane
#       row, block-local).  Zero epilogue, but the fixed stride tracks
#       spatial smoothness less well; see results/bench_compression.json.

def _spatial_delta_apply(q_seg, shape, n):
    """int8 (nbs*block,) segment -> uint8 mod-256 delta'd segment."""
    axis = spatial_delta_axis(shape)
    if axis is None:
        return q_seg.astype(jnp.uint8)          # wraps mod 256 (bit view)
    R = _delta_stride(shape, axis)
    qi = q_seg[:n].astype(jnp.int32)
    prev = jnp.concatenate([jnp.zeros((R,), jnp.int32), qi[:-R]]) \
        if R < n else jnp.zeros((n,), jnp.int32)
    d = ((qi - prev) % 256).astype(jnp.uint8)
    return jnp.concatenate([d, q_seg[n:].astype(jnp.uint8)])


def _spatial_delta_invert(d_seg, shape, n, delta_axis):
    """uint8 segment -> int8 quantized grid (inverse of the above)."""
    if delta_axis is None:
        return d_seg.astype(jnp.int8)
    R = _delta_stride(shape, delta_axis)
    chains = d_seg[:n].astype(jnp.int32).reshape(n // R, R)
    acc = jnp.cumsum(chains, axis=0) % 256
    q = (acc - jnp.where(acc > 127, 256, 0)).astype(jnp.int8).reshape(-1)
    return jnp.concatenate([q, d_seg[n:].astype(jnp.int8)])


def _encode_leaves(leaves, block: int, delta: bool, layout: str):
    """Traceable encode body shared by every fused entry point: pack the
    leaves into one block-aligned stream, quantize in a single launch, and
    (for 'spatial') apply the per-leaf delta epilogue in the same trace."""
    segs, spans = [], []
    for x in leaves:
        flat = jnp.asarray(x).astype(jnp.float32).reshape(-1)
        pad = (-flat.shape[0]) % block
        if pad:
            flat = jnp.pad(flat, (0, pad))
        segs.append(flat)
        spans.append(flat.shape[0])
    total = sum(spans)
    if total == 0:
        return (jnp.zeros((0,), jnp.uint8 if delta else jnp.int8),
                jnp.zeros((0,), jnp.float32))
    flat = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
    if not delta or layout == "block":
        return ops.codec_encode(flat, block=block, delta=delta)
    q, scales = ops.codec_encode(flat, block=block, delta=False)
    outs, off = [], 0
    for x, span in zip(leaves, spans):
        outs.append(_spatial_delta_apply(
            jax.lax.slice(q, (off,), (off + span,)),
            tuple(x.shape), int(x.size)))
        off += span
    return jnp.concatenate(outs), scales


@functools.lru_cache(maxsize=64)
def _fused_encode_fn(block: int, delta: bool, layout: str):
    @jax.jit
    def encode(leaves):
        return _encode_leaves(leaves, block, delta, layout)
    return encode


# keyed on the producer OBJECT (a cached jitted closure from e.g.
# models/swin.head_apply_jit, so identity is stable across frames); bounded
# only to stop executable accumulation if a caller churns through ad-hoc
# producers
@functools.lru_cache(maxsize=64)
def _fused_producer_encode_fn(producer, block: int, delta: bool, layout: str):
    """ONE jitted call running producer(params, inputs) AND the quant
    epilogue: the boundary activations are consumed straight out of the
    producer's trace -- no second dispatch, no intermediate host hop.
    Returns (tree, stream, scales)."""
    @jax.jit
    def run(params, inputs):
        tree = producer(params, inputs)
        leaves = tuple(jnp.asarray(x) for x in jax.tree.leaves(tree))
        stream, scales = _encode_leaves(leaves, block, delta, layout)
        return tree, stream, scales
    return run


# bounded: adaptive cell runs produce a new segment layout whenever a
# slot's batch-group composition changes, and each layout needs its own
# trace anyway -- the cap just stops closure/executable accumulation over
# very long heterogeneous runs (steady-state groups stay cached)
@functools.lru_cache(maxsize=256)
def _fused_decode_fn(segments, block: int, delta: bool, layout: str):
    """segments: per-leaf (shape, dtype, n, block_start, delta_axis)."""
    @jax.jit
    def decode(stream, scales):
        if scales.shape[0] == 0:
            flat = jnp.zeros((0,), jnp.float32)
        elif delta and layout != "block":
            qsegs = []
            for shape, _, n, start, axis in segments:
                span = block * (-(-n // block) if n else 0)
                qsegs.append(_spatial_delta_invert(
                    jax.lax.slice(stream, (start * block,),
                                  (start * block + span,)), shape, n, axis))
            q = jnp.concatenate(qsegs)
            flat = ops.codec_decode(q, scales, block=block, delta=False)
        else:
            flat = ops.codec_decode(stream, scales, block=block, delta=delta)
        leaves = []
        for shape, dtype, n, start, _ in segments:
            seg = jax.lax.slice(flat, (start * block,), (start * block + n,))
            leaves.append(seg.reshape(shape).astype(jnp.dtype(dtype)))
        return leaves
    return decode


def _segment_metas(leaves, block: int,
                   record_delta: bool) -> Tuple[List[TensorMeta], int, int]:
    """Per-leaf stream bookkeeping.  Returns (metas, raw_bytes, n_blocks)."""
    metas, raw, start = [], 0, 0
    for x in leaves:
        nb = -(-x.size // block) if x.size else 0
        metas.append(TensorMeta(
            tuple(x.shape), str(x.dtype), int(x.size), nb, block,
            delta_axis=(spatial_delta_axis(tuple(x.shape))
                        if record_delta else None),
            block_start=start))
        raw += x.size * x.dtype.itemsize
        start += nb
    return metas, raw, start


def _to_host(stream, scales) -> Tuple[np.ndarray, np.ndarray]:
    """The encoded stream and its scales, once ready on the device, in
    one device-to-host transfer."""
    with host_span("codec.wait"):
        jax.block_until_ready((stream, scales))
    with host_span("copy.codec_d2h", bytes=stream.nbytes + scales.nbytes):
        return jax.device_get((stream, scales))


def _to_device(stream: np.ndarray, scales: np.ndarray):
    """The received stream and scales in one host-to-device transfer."""
    with host_span("copy.codec_h2d", bytes=stream.nbytes + scales.nbytes):
        return jax.device_put((stream, scales))


# ---------------------------------------------------------------------------
# host entropy stage: fixed-size zlib chunks on one thread pool
# ---------------------------------------------------------------------------

_POOL: Optional[Tuple[ThreadPoolExecutor, int]] = None
_POOL_LOCK = threading.Lock()


def _pool() -> Tuple[ThreadPoolExecutor, int]:
    """The process's zlib pool and its width, made on first use: one
    thread per usable CPU, at most 16."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            width = min(len(os.sched_getaffinity(0)), 16)
            _POOL = (ThreadPoolExecutor(width, thread_name_prefix="zlib"),
                     width)
        return _POOL


def _pool_map(fn, jobs: Sequence[Any]) -> Tuple[List[Any], int]:
    """``fn`` over ``jobs`` in order, and how many threads ran them: one
    job runs inline, more go to the pool."""
    if len(jobs) <= 1:
        return [fn(j) for j in jobs], 1
    pool, width = _pool()
    return list(pool.map(fn, jobs)), min(width, len(jobs))


def _chunks(stream: np.ndarray, block: int) -> List[memoryview]:
    """A payload's int8 stream as views of its consecutive ranges of
    ``CHUNK_BLOCKS`` quant blocks (the last may be short; an empty stream
    is one empty range)."""
    mv = memoryview(stream).cast("B")
    step = CHUNK_BLOCKS * block
    return [mv[i:i + step] for i in range(0, len(mv), step)] or [mv]


def _deflate_chunk(job: Tuple[memoryview, int]) -> Tuple[bytes, float]:
    buf, level = job
    t0 = time.perf_counter()
    # ``zlib`` looked up per call, so a wrap of the module's name sees it
    blob = zlib.compress(buf, level)
    return blob, time.perf_counter() - t0


def _inflate_chunk(blob: bytes) -> Tuple[bytes, float]:
    t0 = time.perf_counter()
    raw = zlib.decompress(blob)
    return raw, time.perf_counter() - t0


def _zlib_pass(fn, jobs, per_payload: Sequence[int], bytes_in: int,
               ) -> Tuple[List[List[Any]], List[float], float]:
    """Run every chunk job of one pass on the pool under one
    ``codec.zlib`` span.  ``per_payload`` counts each payload's jobs, in
    order.  Returns each payload's outputs, each payload's summed chunk
    seconds, and the span's wall seconds."""
    with host_span("codec.zlib", payloads=len(per_payload),
                   chunks=len(jobs), bytes_in=bytes_in) as sp:
        done, threads = _pool_map(fn, jobs)
        sp.set(threads=threads, bytes_out=sum(len(o) for o, _ in done))
    outs, secs, i = [], [], 0
    for n in per_payload:
        part = done[i:i + n]
        outs.append([o for o, _ in part])
        secs.append(sum(t for _, t in part))
        i += n
    return outs, secs, sp.seconds


def _inflate(ps: Sequence["CompressedPayload"]) -> np.ndarray:
    """The fused payloads' streams, concatenated in order: every chunk of
    every payload inflated in one pass (mode 'int8' blobs are the stream
    as it is)."""
    if ps[0].mode == "int8":
        raws = [b for p in ps for b in p.blobs]
    else:
        blobs = [b for p in ps for b in p.blobs]
        outs, _, _ = _zlib_pass(_inflate_chunk, blobs,
                                [len(p.blobs) for p in ps],
                                sum(map(len, blobs)))
        raws = [r for out in outs for r in out]
    delta = ps[0].mode == "int8_delta_zlib"
    return np.frombuffer(b"".join(raws), dtype=np.uint8 if delta else np.int8)


@dataclass
class ActivationCodec:
    """INT8+zlib codec with payload accounting.

    quant_block: elements per absmax block (one f32 scale per block).
    level: zlib level (1 = paper's 'rapid' setting).
    mode: 'int8_zlib' (paper) | 'int8' (quant only) | 'zlib' (no quant)
          | 'raw' (accounting only)
          | 'int8_delta_zlib' (beyond-paper: lossless mod-256 delta filter
            on the quantized grid before zlib -- feature maps are smooth,
            so the filtered int8 stream is far more compressible: 88.4%
            vs 78.6% reduction on Swin split-1 activations; DESIGN.md §5
            and results/bench_compression.json).
    fused: encode int8-family payloads with the single-launch fused
           kernel path (default).  ``fused=False`` keeps the legacy
           per-tensor loop; decode always honors the payload's own
           layout, so either side may flip the flag independently.
    delta_layout: fused delta geometry -- 'spatial' (legacy-equivalent
           per-leaf row delta fused into the encode executable; best
           ratio) or 'block' (fully in-register per grid step inside the
           Pallas kernel; zero epilogue, slightly worse ratio).
    """
    quant_block: int = 8192
    level: int = 1
    mode: str = "int8_zlib"
    fused: bool = True
    delta_layout: str = "spatial"

    def _use_fused(self) -> bool:
        if self.mode in _INT8_MODES and self.quant_block % 128:
            # both encoders tile the stream into 128-lane rows (the legacy
            # kernel asserts the same thing deeper down, less readably)
            raise ValueError(f"quant_block must be a multiple of 128 (TPU "
                             f"lane width); got {self.quant_block}")
        return self.fused and self.mode in _INT8_MODES

    def _deflate(self, streams: Sequence[np.ndarray]
                 ) -> Tuple[List[List[bytes]], List[float], float]:
        """Each payload's int8 stream as its chunk blobs, every chunk of
        the pass deflated in one pool pass (mode 'int8' ships a stream as
        one blob, as it is).  Returns the blobs, each payload's summed
        chunk seconds, and the pass's wall seconds."""
        if self.mode == "int8":
            return [[s.tobytes()] for s in streams], [0.0] * len(streams), 0.0
        cuts = [_chunks(s, self.quant_block) for s in streams]
        return _zlib_pass(_deflate_chunk,
                          [(c, self.level) for cs in cuts for c in cs],
                          [len(cs) for cs in cuts],
                          sum(s.nbytes for s in streams))

    @staticmethod
    def _set_encode_s(ps: Sequence[CompressedPayload], encode_s: float,
                      zlib_s: float, chunk_s: Sequence[float]):
        """Each payload's share of the pass outside zlib, plus its own
        chunks' deflate seconds (``CompressedPayload.encode_s``)."""
        for p, own in zip(ps, chunk_s):
            p.encode_s = (encode_s - zlib_s) / len(ps) + own

    # -- compress -----------------------------------------------------------
    def compress(self, tree) -> CompressedPayload:
        with host_span("codec.encode", frames=1) as sp:
            if self._use_fused():
                p, zlib_s, chunk_s = self._compress_fused(tree)
            else:
                p, zlib_s, chunk_s = self._compress_legacy(tree), 0.0, 0.0
        self._set_encode_s([p], sp.seconds, zlib_s, [chunk_s])
        return p

    def _compress_fused(self, tree) -> Tuple[CompressedPayload, float, float]:
        leaves, treedef = jax.tree.flatten(tree)
        leaves = [jnp.asarray(x) for x in leaves]
        delta = self.mode == "int8_delta_zlib"
        stream, scales = _fused_encode_fn(
            self.quant_block, delta, self.delta_layout)(tuple(leaves))
        stream, scales = _to_host(stream, scales)           # one transfer
        metas, raw, _ = _segment_metas(
            leaves, self.quant_block,
            record_delta=delta and self.delta_layout == "spatial")
        (blobs,), (chunk_s,), zlib_s = self._deflate([stream])
        return (CompressedPayload(blobs, [scales], metas, raw, treedef,
                                  mode=self.mode, fused=True,
                                  delta_layout=self.delta_layout if delta
                                  else None), zlib_s, chunk_s)

    def _compress_legacy(self, tree) -> CompressedPayload:
        leaves, treedef = jax.tree.flatten(tree)
        blobs, scales, metas = [], [], []
        raw = 0
        for x in leaves:
            x = jnp.asarray(x)
            raw += x.size * x.dtype.itemsize
            if self.mode == "raw":
                blobs.append(np.asarray(x).tobytes())
                scales.append(np.zeros((0,), np.float32))
                metas.append(TensorMeta(x.shape, str(x.dtype), x.size, 0, 0))
                continue
            if self.mode == "zlib":
                blobs.append(zlib.compress(np.asarray(x).tobytes(), self.level))
                scales.append(np.zeros((0,), np.float32))
                metas.append(TensorMeta(x.shape, str(x.dtype), x.size, 0, 0))
                continue
            q, s, n = ops.quantize(x, block=self.quant_block)
            q_np = np.asarray(q)
            delta_axis = (spatial_delta_axis(tuple(x.shape))
                          if self.mode == "int8_delta_zlib" else None)
            if self.mode == "int8":
                payload = q_np.tobytes()
            elif delta_axis is not None:
                img = q_np.reshape(-1)[:x.size].reshape(x.shape)
                # exact mod-256 delta (d[0] = x[0], so reconstruction is
                # a cumsum mod 256 -- lossless)
                d16 = np.diff(img.astype(np.int16), axis=delta_axis,
                              prepend=np.zeros_like(
                                  np.take(img, [0], axis=delta_axis), np.int16))
                d = (d16 % 256).astype(np.uint8)
                tail = q_np.reshape(-1)[x.size:]      # block padding
                payload = zlib.compress(d.tobytes() + tail.tobytes(), self.level)
            else:
                payload = zlib.compress(q_np.tobytes(), self.level)
            blobs.append(payload)
            scales.append(np.asarray(s))
            metas.append(TensorMeta(tuple(x.shape), str(x.dtype), int(n),
                                    int(q.shape[0]), int(q.shape[1]),
                                    delta_axis=delta_axis))
        return CompressedPayload(blobs, scales, metas, raw, treedef,
                                 mode=self.mode)

    # -- fused head->encode (one device call for model + quant) --------------
    def supports_fused(self) -> bool:
        """True when this codec's mode runs the single-stream fused layout
        (the precondition for ``compress_head``)."""
        return self._use_fused()

    def compress_head(self, producer, params, inputs):
        """Run ``producer(params, inputs)`` (a stable jitted callable, e.g.
        ``SwinSplitPlan.head_jitted``) with the int8 quant epilogue fused
        into the SAME jitted computation, so encode starts on-device with
        zero extra passes.  Returns (CompressedPayload, producer_tree).

        Byte-identity: the fused trace embeds the producer's own trace
        unchanged and the packed stream leaves the device through the same
        ``_encode_leaves`` graph ``compress`` uses, so blobs/scales/metas
        are byte-identical to ``compress(producer(params, inputs))``
        (pinned across every split in tests/test_swin.py)."""
        if not self._use_fused():
            tree = producer(params, inputs)
            return self.compress(tree), tree
        delta = self.mode == "int8_delta_zlib"
        with host_span("codec.encode", frames=1) as sp:
            tree, stream, scales = _fused_producer_encode_fn(
                producer, self.quant_block, delta, self.delta_layout)(
                params, inputs)
            leaves, treedef = jax.tree.flatten(tree)
            stream, scales = _to_host(stream, scales)       # one transfer
            metas, raw, _ = _segment_metas(
                leaves, self.quant_block,
                record_delta=delta and self.delta_layout == "spatial")
            (blobs,), (chunk_s,), zlib_s = self._deflate([stream])
        p = CompressedPayload(blobs, [scales], metas, raw, treedef,
                              mode=self.mode, fused=True,
                              delta_layout=self.delta_layout if delta
                              else None)
        self._set_encode_s([p], sp.seconds, zlib_s, [chunk_s])
        return p, tree

    # -- batch-group compress (one launch across many payloads) -------------
    def compress_group(self, trees: Sequence[Any]) -> List[CompressedPayload]:
        """Encode many payloads in ONE device pass.

        The packed stream keeps every leaf's own quant blocks, and each
        payload's byte range is cut into chunks from its own start, all
        payloads' chunks deflated in one pool pass, so the returned
        payloads are byte-identical to per-payload ``compress`` -- the
        per-UE uplink accounting (and the receiver) can't tell the
        difference; only the encoder's wall clock can."""
        if not trees or len(trees) == 1 or not self._use_fused():
            return [self.compress(t) for t in trees]
        delta = self.mode == "int8_delta_zlib"
        with host_span("codec.encode", frames=len(trees)) as sp:
            flat: List[Any] = []
            per_tree = []
            for t in trees:
                leaves, treedef = jax.tree.flatten(t)
                leaves = [jnp.asarray(x) for x in leaves]
                per_tree.append((leaves, treedef))
                flat.extend(leaves)
            stream, scales = _fused_encode_fn(
                self.quant_block, delta, self.delta_layout)(tuple(flat))
            stream, scales = _to_host(stream, scales)
            parts, start = [], 0
            for leaves, treedef in per_tree:
                metas, raw, nb = _segment_metas(
                    leaves, self.quant_block,
                    record_delta=delta and self.delta_layout == "spatial")
                parts.append((metas, raw, treedef, start, start + nb))
                start += nb
            blobs, chunk_s, zlib_s = self._deflate(
                [stream[a * self.quant_block:b * self.quant_block]
                 for *_, a, b in parts])
            out = [CompressedPayload(
                blob, [scales[a:b].copy()], metas, raw, treedef,
                mode=self.mode, fused=True,
                delta_layout=self.delta_layout if delta else None)
                for blob, (metas, raw, treedef, a, b) in zip(blobs, parts)]
        self._set_encode_s(out, sp.seconds, zlib_s, chunk_s)
        return out

    # -- decompress ----------------------------------------------------------
    def decompress(self, p: CompressedPayload):
        with host_span("codec.decode", frames=1):
            if p.fused:
                return self._decompress_fused(p)
            return self._decompress_legacy(p)

    def _decompress_fused(self, p: CompressedPayload):
        delta = p.mode == "int8_delta_zlib"
        block = p.meta[0].block if p.meta else self.quant_block
        segments = tuple((m.shape, m.dtype, m.n, m.block_start, m.delta_axis)
                         for m in p.meta)
        leaves = _fused_decode_fn(segments, block, delta,
                                  p.delta_layout or "block")(
            *_to_device(_inflate([p]), p.scales[0]))
        return jax.tree.unflatten(p.treedef, leaves)

    def decompress_group(self, ps: Sequence[CompressedPayload]) -> List[Any]:
        """Decode many fused payloads with one upload + one launch (the
        edge side of ``compress_group``).  The decoded leaves stay device-
        resident, ready to feed ``SplitPlan.tail_batched`` directly."""
        if len(ps) <= 1 or not all(p.fused for p in ps):
            return [self.decompress(p) for p in ps]
        kinds = {(p.mode, p.delta_layout) for p in ps} \
            | {("block", m.block) for p in ps for m in p.meta}
        if len(kinds) > 2:      # one (mode, layout) + one ("block", size)
            raise ValueError(f"group mixes codec settings: {sorted(kinds)}; "
                             "decompress_group needs one mode/layout/block")
        delta = ps[0].mode == "int8_delta_zlib"
        block = next((m.block for p in ps for m in p.meta), self.quant_block)
        segments, start = [], 0
        for p in ps:
            for m in p.meta:
                segments.append((m.shape, m.dtype, m.n,
                                 start + m.block_start, m.delta_axis))
            start += sum(m.n_blocks for m in p.meta)
        with host_span("codec.decode", frames=len(ps)):
            stream = _inflate(ps)
            scales = np.concatenate([p.scales[0] for p in ps])
            leaves = _fused_decode_fn(tuple(segments), block, delta,
                                      ps[0].delta_layout or "block")(
                *_to_device(stream, scales))
        out, off = [], 0
        for p in ps:
            out.append(jax.tree.unflatten(p.treedef,
                                          leaves[off:off + len(p.meta)]))
            off += len(p.meta)
        return out

    def _decompress_legacy(self, p: CompressedPayload):
        # the payload is self-describing: honor the mode it was encoded
        # with, not whatever this codec instance happens to default to
        mode = p.mode if p.mode is not None else self.mode
        leaves = []
        for blob, s, m in zip(p.blobs, p.scales, p.meta):
            if mode == "raw":
                x = np.frombuffer(blob, dtype=m.dtype).reshape(m.shape)
                leaves.append(jnp.asarray(x))
                continue
            if mode == "zlib":
                x = np.frombuffer(zlib.decompress(blob), dtype=m.dtype)
                leaves.append(jnp.asarray(x.reshape(m.shape)))
                continue
            raw = blob if mode == "int8" else zlib.decompress(blob)
            if mode == "int8_delta_zlib" and len(m.shape) >= 3:
                n_valid = int(np.prod(m.shape))
                d = np.frombuffer(raw[:n_valid], dtype=np.uint8).reshape(m.shape)
                axis = (m.delta_axis if m.delta_axis is not None
                        else (1 if m.shape[0] < 4 else 0))
                img = (np.cumsum(d.astype(np.int64), axis=axis) % 256
                       ).astype(np.uint8).view(np.int8)
                tail = np.frombuffer(raw[n_valid:], dtype=np.int8)
                raw = img.tobytes() + tail.tobytes()
            q = np.frombuffer(raw, dtype=np.int8).reshape(m.n_blocks, m.block)
            x = ops.dequantize(jnp.asarray(q), jnp.asarray(s), m.n, m.shape,
                               jnp.dtype(m.dtype))
            leaves.append(x)
        return jax.tree.unflatten(p.treedef, leaves)

    # -- accounting-only (no host roundtrip; used by the controller) ---------
    #
    # Default entropy-coding ratios per mode when no measured feedback is
    # available yet: 0.55 on the int8 stream is the paper's rapid-zlib
    # operating point; the delta filter's measured cold-start ratio on
    # Swin split payloads is ~0.47 of the int8 stream (an 88% reduction
    # of raw f32: (1-0.88)*4 bytes/elem ~= 0.47 int8 bytes/elem --
    # results/bench_compression.json); raw f32 barely compresses (~0.9).
    DEFAULT_RATIOS = {"int8_zlib": 0.55, "int8_delta_zlib": 0.47, "zlib": 0.90}

    def estimate_bytes(self, shapes_dtypes, measured_ratio: Optional[float] = None):
        """Predict compressed payload size from tensor specs.

        measured_ratio: zlib ratio observed on recent frames (the
        controller feeds back actual ratios).  It applies to the int8
        stream for the int8* modes and to the raw float bytes for
        'zlib'; defaults are mode-aware (DEFAULT_RATIOS)."""
        raw = sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes_dtypes)
        if self.mode == "raw":
            return raw
        if self.mode == "zlib":
            r = (measured_ratio if measured_ratio is not None
                 else self.DEFAULT_RATIOS["zlib"])
            return int(raw * r)
        n_elems = sum(int(np.prod(s)) for s, _ in shapes_dtypes)
        int8 = n_elems + 4 * (n_elems // self.quant_block + len(shapes_dtypes))
        if self.mode == "int8":
            return int8
        r = (measured_ratio if measured_ratio is not None
             else self.DEFAULT_RATIOS[self.mode])
        return int(int8 * r)
