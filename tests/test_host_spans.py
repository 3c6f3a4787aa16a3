"""Wall-clock host spans of the served path (core/telemetry.py, host
spans) and the stable names of the model programs: a small Swin cell
through ``CellSimulator.run_stream``, split2 with the MAC and server_only
without it."""
import math
import threading
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs.swin_t_detection import reduced
from repro.core import calibration as C
from repro.core import compression as CC
from repro.core import telemetry as T
from repro.core.cell import CellSimulator
from repro.core.compression import ActivationCodec
from repro.core.ran import RanCell, RanConfig, make_policy
from repro.core.splitting import SERVER_ONLY, SwinSplitPlan
from repro.models import swin as SW

N_UES = 2
LEVELS = np.array([[-40.0, -20.0]])


@pytest.fixture(scope="module")
def swin():
    cfg = reduced()
    params = SW.init(cfg, jax.random.PRNGKey(0))
    imgs = [np.asarray(jax.random.uniform(jax.random.PRNGKey(i),
                                          (1, cfg.img_h, cfg.img_w, 3)))
            for i in range(N_UES)]
    return cfg, params, imgs


def _sim(swin, option, observed=None):
    """The cell as the benchmark builds it: fixed option, every batch the
    largest bucket, which closes only when full.  ``observed`` counts the
    MAC policy's per-TTI ``observe`` calls."""
    cfg, params, _ = swin
    ran = None
    if option != SERVER_ONLY:
        policy = make_policy("edf")
        if observed is not None:
            orig = policy.observe

            def observe(*a, **k):
                observed.append(1)
                return orig(*a, **k)
            policy.observe = observe
        ran = RanCell(policy, RanConfig(n_prbs=100, tti_s=1e-3))
    buckets = (1, 2) if option != SERVER_ONLY else (1,)
    return CellSimulator(plan=SwinSplitPlan(cfg, params),
                         system=C.calibrate(), n_ues=N_UES,
                         codec=ActivationCodec(), controller=None,
                         execute_model=True, buckets=buckets,
                         max_wait_s=30.0, ran=ran, engine="python")


def _round(swin, option, observed=None):
    sim = _sim(swin, option, observed)
    res = sim.run_stream(LEVELS, imgs=swin[2], option=option,
                         keep_outputs=True)
    jax.block_until_ready(res.outputs)
    return res


@pytest.fixture(scope="module")
def split2(swin):
    _round(swin, "split2")                       # compile outside the record
    observed = []
    rec = T.HostRecorder()
    with T.recording(rec):
        res = _round(swin, "split2", observed)
    return res, rec.spans, len(observed)


@pytest.fixture(scope="module")
def server_only(swin):
    _round(swin, SERVER_ONLY)
    rec = T.HostRecorder()
    with T.recording(rec):
        res = _round(swin, SERVER_ONLY)
    return res, rec.spans


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def _blocks_per_payload(swin):
    plan = SwinSplitPlan(swin[0], None)
    return sum(math.ceil(math.prod(shape) / ActivationCodec().quant_block)
               for shape, _ in plan.payload_specs("split2"))


def _chunks_per_payload(swin):
    return math.ceil(_blocks_per_payload(swin) / CC.CHUNK_BLOCKS)


def test_split2_records_every_span_once_per_occurrence(swin, split2):
    _, spans, _ = split2
    got = Counter((s.name, s.parent) for s in spans)
    assert got == Counter({
        ("engine.run_stream", None): 1,
        ("head", "engine.run_stream"): N_UES,
        ("copy.frame_h2d", "head"): N_UES,
        ("codec.encode", "engine.run_stream"): 1,      # one group encode
        ("codec.wait", "codec.encode"): 1,
        ("copy.codec_d2h", "codec.encode"): 1,
        ("codec.zlib", "codec.encode"): 1,             # one pass for both
        ("codec.decode", "engine.run_stream"): 1,
        ("codec.zlib", "codec.decode"): 1,
        ("copy.codec_h2d", "codec.decode"): 1,
        ("mac.advance", "engine.run_stream"):
            got[("mac.advance", "engine.run_stream")],
        ("tail", "engine.run_stream"): 1,              # one batch of two
        ("tail.stack", "tail"): 1,
        ("tail.dispatch", "tail"): 1,
        ("tail.unstack", "tail"): 1,
    })
    assert got[("mac.advance", "engine.run_stream")] >= 1
    by = _by_name(spans)
    assert sorted((s.attrs["ue"], s.attrs["frame"]) for s in by["head"]) \
        == [(0, 0), (1, 0)]
    assert by["codec.encode"][0].attrs == {"frames": N_UES}
    assert by["codec.decode"][0].attrs == {"frames": N_UES}
    assert by["tail"][0].attrs == {"frames": N_UES, "batch": N_UES}
    for s in by["codec.zlib"]:
        assert s.attrs["bytes_in"] > 0 and s.attrs["bytes_out"] > 0
        assert s.attrs["payloads"] == N_UES
        assert s.attrs["chunks"] == N_UES * _chunks_per_payload(swin)
        assert s.attrs["threads"] == min(CC._pool()[1], s.attrs["chunks"])
    for s in spans:
        assert s.t0 <= s.t1 and s.thread == threading.get_ident()
    outer = by["engine.run_stream"][0]
    assert all(outer.t0 <= s.t0 and s.t1 <= outer.t1 for s in spans)


def test_copy_bytes_are_what_crosses(swin, split2):
    cfg, _, imgs = swin
    res, spans, _ = split2
    by = _by_name(spans)
    assert [s.attrs["bytes"] for s in by["copy.frame_h2d"]] == \
        [im.nbytes for im in imgs]
    # the int8 stream and the f32 scales of both payloads, in one download
    block = ActivationCodec().quant_block
    n_blocks = N_UES * _blocks_per_payload(swin)
    assert by["copy.codec_d2h"][0].attrs["bytes"] == n_blocks * (block + 4)
    assert by["copy.codec_h2d"][0].attrs["bytes"] == n_blocks * (block + 4)
    # what zlib wrote, plus the scales, is what each UE sent
    (enc,) = [s for s in by["codec.zlib"] if s.parent == "codec.encode"]
    (dec,) = [s for s in by["codec.zlib"] if s.parent == "codec.decode"]
    sent = sum(l.compressed_bytes for l in res.logs)
    assert enc.attrs["bytes_out"] + 4 * n_blocks == sent
    assert enc.attrs["bytes_in"] == n_blocks * block
    # decode inflates exactly what encode deflated
    assert (dec.attrs["bytes_in"], dec.attrs["bytes_out"]) == \
        (enc.attrs["bytes_out"], enc.attrs["bytes_in"])


def test_ttis_are_the_mac_s_own_steps(split2):
    _, spans, observed = split2
    ttis = [s.attrs["ttis"] for s in spans if s.name == "mac.advance"]
    assert sum(ttis) == observed > 0


def test_quant_s_is_the_share_of_the_encode_span(split2):
    """Each UE is charged its share of the encode pass outside zlib plus
    the seconds its own chunks took to deflate; the engine gives every UE
    of the group the mean.  So the UEs' summed deflate seconds are what
    their ``quant_s`` holds beyond the non-zlib share: more than nothing,
    and no more than the pass's threads could run in its wall time."""
    res, spans, _ = split2
    by = _by_name(spans)
    enc = by["codec.encode"][0]
    (zl,) = [s for s in by["codec.zlib"] if s.parent == "codec.encode"]
    quant_s = {log.quant_s for log in res.logs}
    assert len(res.logs) == N_UES and len(quant_s) == 1
    deflate_s = N_UES * quant_s.pop() - (enc.seconds - zl.seconds)
    assert 0.0 < deflate_s <= zl.seconds * zl.attrs["threads"]


def test_server_only_uploads_the_frame_in_the_tail_stack(swin, server_only):
    _, _, imgs = swin
    _, spans = server_only
    got = Counter((s.name, s.parent) for s in spans)
    assert got == Counter({
        ("engine.run_stream", None): 1,
        ("head", "engine.run_stream"): N_UES,          # nothing to run
        ("tail", "engine.run_stream"): N_UES,          # batches of one
        ("tail.stack", "tail"): N_UES,
        ("copy.frame_h2d", "tail.stack"): N_UES,
        ("tail.dispatch", "tail"): N_UES,
        ("tail.unstack", "tail"): N_UES,
    })
    up = [s.attrs["bytes"] for s in spans if s.name == "copy.frame_h2d"]
    assert sorted(up) == sorted(im.nbytes for im in imgs)
    assert all(s.attrs == {"frames": 1, "batch": 1}
               for s in spans if s.name == "tail")


@pytest.mark.parametrize("option", ["split2", SERVER_ONLY])
def test_nothing_attached_keeps_nothing_and_changes_no_answer(
        swin, split2, server_only, option, monkeypatch):
    attached = (split2 if option == "split2" else server_only)[0]
    monkeypatch.setattr(T, "_RECORDER", None)
    plain = _round(swin, option)
    assert T._RECORDER is None
    rec = T.HostRecorder()
    with T.recording(rec):                 # no backlog kept for it
        pass
    assert rec.spans == []
    a, b = attached.outputs[0], plain.outputs[0]
    assert sorted(a) == sorted(b) == list(range(N_UES))
    for u in a:
        la, lb = jax.tree.leaves(a[u]), jax.tree.leaves(b[u])
        assert len(la) == len(lb) > 0
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_a_worker_thread_has_its_own_parent_chain():
    prev = T._RECORDER
    rec = T.HostRecorder()
    seen = {}

    def work():
        seen["ident"] = threading.get_ident()
        with T.host_span("codec.zlib", bytes_in=3) as sp:
            with T.host_span("inner"):
                pass
            sp.set(bytes_out=2)

    with T.recording(rec):
        with T.host_span("codec.encode"):
            th = threading.Thread(target=work)
            th.start()
            th.join()
    by = {s.name: s for s in rec.spans}
    assert by["codec.zlib"].parent is None
    assert by["inner"].parent == "codec.zlib"
    assert by["codec.encode"].parent is None
    assert by["codec.zlib"].thread == by["inner"].thread == seen["ident"]
    assert by["codec.encode"].thread == threading.get_ident()
    assert by["codec.zlib"].attrs == {"bytes_in": 3, "bytes_out": 2}
    assert T._RECORDER is prev


def test_span_seconds_without_a_recorder():
    with T.host_span("x") as sp:
        pass
    assert sp.seconds >= 0.0 and sp.parent is None and sp.t1 >= sp.t0


def test_lowered_programs_carry_their_names(swin):
    cfg, params, imgs = swin
    img = imgs[0]
    head = SW.head_apply_jit(cfg, 2).lower(params, img)
    assert "@jit_swin_head" in head.as_text()
    plan = SwinSplitPlan(cfg, params)
    payload = jax.eval_shape(lambda p, x: SW.head_apply(cfg, p, x, 2),
                             params, img)
    tail = plan._tail_jitted("split2").lower(params, payload)
    assert "@jit_swin_tail" in tail.as_text()
    full = SW.forward_full_jit(cfg).lower(params, img)
    assert "@jit_swin_full" in full.as_text()
