"""Split plans: partition an unmodified model's forward pass at a boundary.

The paper's mechanism, generalized over the model zoo behind one
``SplitPlan`` protocol (options, head/tail execution, flop + payload
accounting, and batched tail execution for the multi-UE cell):

  * ``SwinSplitPlan`` -- the paper's own setting: split the Swin detection
    backbone at {after patch-embed, after stage 1..4}; the FPN/RPN-style
    head always runs server-side (paper §IV-A).  Execution options follow
    paper Fig. 4: UE_ONLY, SPLIT(l), SERVER_ONLY.

  * ``LMSplitPlan`` -- the technique applied to the assigned LM archs: the
    residual stream is cut at a layer boundary; deployment-friendly
    candidates default to quartile depths.  For SSM/hybrid archs the
    recurrent state of head-side layers is part of the handoff payload
    (accounted by ``payload_specs``) -- see DESIGN.md §Arch-applicability.

Per-frame workload differences between the families (an image frame vs. an
``n_tokens`` LM prefill) live in a ``Workload`` descriptor attached to the
plan, so every accounting method takes only ``option`` and anything above
this layer (pipeline, cell simulator, adaptive controller) is plan-generic.

No retraining, no weight surgery: head and tail tree-slice the *same*
parameter pytree.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import (Any, Dict, List, Optional, Protocol, Sequence, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.swin_t_detection import SwinConfig
from repro.core.telemetry import host_span
from repro.models import swin as SW
from repro.models import transformer as T

UE_ONLY = "ue_only"
SERVER_ONLY = "server_only"


def split_option(l: int) -> str:
    return f"split{l}"


# ===========================================================================
# The protocol + shared machinery
# ===========================================================================

@dataclass(frozen=True)
class Workload:
    """What one frame of work means for a plan.

    Swin processes one image per frame (``n_tokens`` unused, kept at 1);
    LM plans process an ``n_tokens`` prefill per frame.  ``include_state``
    adds the recurrent state of head-side SSM/hybrid layers to the payload
    accounting (it must ship whenever the split point moves).
    """
    n_tokens: int = 1
    include_state: bool = False


@runtime_checkable
class SplitPlan(Protocol):
    """Uniform interface every split plan implements.

    ``head``/``tail`` execute the partitioned forward; ``tail_batched``
    stacks same-option payloads from many UEs and runs ONE jitted tail
    forward (the edge server's micro-batching entry); the ``*_flops`` /
    ``payload_specs`` family is pure accounting over ``self.workload``.
    """
    params: Any
    workload: Workload

    @property
    def options(self) -> List[str]: ...
    def head(self, inputs, option: str) -> Tuple[Any, Any]: ...
    def tail(self, payload, option: str) -> Any: ...
    def tail_batched(self, payloads: Sequence[Any], option: str,
                     pad_to: Optional[int] = None) -> List[Any]: ...
    def head_flops(self, option: str) -> float: ...
    def tail_flops(self, option: str) -> float: ...
    def payload_specs(self, option: str) -> List[Tuple[Tuple[int, ...], str]]: ...
    def raw_payload_bytes(self, option: str, batch: int = 1) -> int: ...


def payload_batch(payload) -> int:
    """Leading (batch) dim of a payload pytree."""
    leaf = jax.tree.leaves(payload)[0]
    return int(leaf.shape[0])


def stack_payloads(payloads: Sequence[Any], pad_to: Optional[int] = None):
    """Concatenate same-structure payloads along the batch axis, optionally
    zero-padding to ``pad_to`` rows (bucketed batch sizes keep the jitted
    tail from retracing on every occupancy)."""
    stacked = jax.tree.map(
        lambda *xs: jnp.concatenate([jnp.asarray(x) for x in xs], axis=0),
        *payloads)
    total = sum(payload_batch(p) for p in payloads)
    if pad_to is not None and pad_to > total:
        pad = pad_to - total
        stacked = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0),
            stacked)
    return stacked


def upload_host_leaves(tree):
    """``tree`` with every host (numpy) leaf put on the device in one
    ``jax.device_put``, inside a ``copy.frame_h2d`` span; device leaves
    pass through, and a tree without host leaves opens no span."""
    leaves, treedef = jax.tree.flatten(tree)
    host = [i for i, x in enumerate(leaves) if isinstance(x, np.ndarray)]
    if not host:
        return tree
    with host_span("copy.frame_h2d",
                   bytes=sum(leaves[i].nbytes for i in host)):
        put = jax.device_put([leaves[i] for i in host])
    for i, x in zip(host, put):
        leaves[i] = x
    return jax.tree.unflatten(treedef, leaves)


def unstack_outputs(out, sizes: Sequence[int]) -> List[Any]:
    """Slice a batched tail output back into per-payload outputs."""
    outs, off = [], 0
    for n in sizes:
        outs.append(jax.tree.map(lambda a, o=off, n=n: a[o:o + n], out))
        off += n
    return outs


class _PlanBase:
    """Shared protocol plumbing: byte accounting and batched tail execution
    on top of each plan's ``payload_specs`` / ``_tail_impl``."""

    def raw_payload_bytes(self, option: str, batch: int = 1) -> int:
        return batch * sum(int(np.prod(s)) * np.dtype(d).itemsize
                           for s, d in self.payload_specs(option))

    def tail(self, payload, option: str):
        return self._tail_impl(self.params, payload, option)

    def tail_batched(self, payloads: Sequence[Any], option: str,
                     pad_to: Optional[int] = None) -> List[Any]:
        """Stack same-option payloads and run ONE jitted tail forward.

        Returns per-payload outputs in input order.  ``pad_to`` zero-pads
        the stacked batch (padding rows are dropped from the outputs); the
        jit cache is keyed per (option, executed batch) by tracing, so
        callers should pad to a small set of bucket sizes.  The call is the
        ``tail`` host span (frames, executed batch), with ``tail.stack``
        (uploading any host leaf first), ``tail.dispatch`` and
        ``tail.unstack`` under it; none waits for the device.
        """
        assert self.params is not None, "tail_batched needs real params"
        sizes = [payload_batch(p) for p in payloads]
        total = sum(sizes)
        with host_span("tail", frames=total, batch=max(pad_to or 0, total)):
            with host_span("tail.stack"):
                stacked = stack_payloads(upload_host_leaves(list(payloads)),
                                         pad_to=pad_to)
            with host_span("tail.dispatch"):
                out = self._tail_jitted(option)(self.params, stacked)
            with host_span("tail.unstack"):
                if pad_to is not None and pad_to > total:
                    out = jax.tree.map(lambda a: a[:total], out)
                return unstack_outputs(out, sizes)

    def _tail_jitted(self, option: str):
        cache = self.__dict__.setdefault("_tail_jit_cache", {})
        if option not in cache:
            def plan_tail(params, payload):
                return self._tail_impl(params, payload, option)
            cache[option] = jax.jit(plan_tail)
        return cache[option]


# ===========================================================================
# Swin (the paper's model)
# ===========================================================================

@dataclass
class SwinSplitPlan(_PlanBase):
    cfg: SwinConfig
    params: Any
    ship_merged: bool = True          # False = beyond-paper payload opt
    include_early_split: bool = False  # split0 (after patch embed, paper §IV-B)
    workload: Workload = field(default_factory=Workload)

    @property
    def options(self) -> List[str]:
        splits = range(0 if self.include_early_split else 1, self.cfg.n_stages + 1)
        return [UE_ONLY] + [split_option(l) for l in splits] + [SERVER_ONLY]

    # -- execution -----------------------------------------------------------
    def head(self, img, option: str):
        """UE-side computation.  Returns (payload_tree_or_None, detections_or_None).

        Runs through the model-level trace caches (``head_apply_jit`` /
        ``forward_full_jit``), so per-frame calls stop retracing.  A host
        frame is uploaded first, in a ``copy.frame_h2d`` span; the
        SERVER_ONLY frame leaves as it came (the edge's tail uploads it)."""
        if option == SERVER_ONLY:
            return {"img": img}, None
        img = upload_host_leaves(img)
        if option == UE_ONLY:
            return None, SW.forward_full_jit(self.cfg)(self.params, img)
        return self.head_jitted(option)(self.params, img), None

    def head_jitted(self, option: str):
        """Cached jitted head producer for ``option`` (None for the two
        degenerate modes, which ship no boundary activations).  The fused
        head->encode stage (core/pipeline.py) traces THIS callable into its
        single device call, so fused and unfused paths share one trace."""
        if option in (UE_ONLY, SERVER_ONLY):
            return None
        l = int(option.removeprefix("split"))
        return SW.head_apply_jit(self.cfg, l, self.ship_merged)

    def _tail_impl(self, params, payload, option: str):
        if option == SERVER_ONLY:
            return SW.forward_full(self.cfg, params, payload["img"])
        l = int(option.removeprefix("split"))
        return SW.tail_apply(self.cfg, params, payload, l)

    def _tail_jitted(self, option: str):
        # share the model-level trace caches across plan instances
        if option == SERVER_ONLY:
            full = SW.forward_full_jit(self.cfg)
            return lambda params, payload: full(params, payload["img"])
        if option != UE_ONLY:
            return SW.tail_apply_jit(self.cfg, int(option.removeprefix("split")))
        return super()._tail_jitted(option)

    # -- accounting ----------------------------------------------------------
    def head_flops(self, option: str) -> int:
        if option == UE_ONLY:
            return SW.total_flops(self.cfg)
        if option == SERVER_ONLY:
            return 0
        return SW.head_flops(self.cfg, int(option.removeprefix("split")))

    def tail_flops(self, option: str) -> int:
        if option == UE_ONLY:
            return 0
        if option == SERVER_ONLY:
            return SW.total_flops(self.cfg)
        return SW.tail_flops(self.cfg, int(option.removeprefix("split")))

    def payload_specs(self, option: str) -> List[Tuple[Tuple[int, ...], str]]:
        """(shape, dtype) per shipped tensor, batch dim excluded."""
        if option == UE_ONLY:
            return []
        if option == SERVER_ONLY:
            return [((self.cfg.img_h, self.cfg.img_w, 3), "uint8")]
        l = int(option.removeprefix("split"))
        return [(s, self.cfg.dtype)
                for s in SW.boundary_shapes(self.cfg, l,
                                            ship_merged=self.ship_merged)]


# ===========================================================================
# LM-family archs (technique generalization)
# ===========================================================================

def default_candidates(cfg: ModelConfig) -> Tuple[int, ...]:
    n = cfg.n_layers
    qs = sorted({min(max(1, round(n * q)), n - 1) for q in (0.25, 0.5, 0.75)})
    return tuple(qs)


@dataclass
class LMSplitPlan(_PlanBase):
    cfg: ModelConfig
    params: Any
    candidates: Tuple[int, ...] = ()
    workload: Workload = field(default_factory=lambda: Workload(n_tokens=128))

    def __post_init__(self):
        if not self.candidates:
            self.candidates = default_candidates(self.cfg)

    @property
    def options(self) -> List[str]:
        return ([UE_ONLY] + [split_option(l) for l in self.candidates]
                + [SERVER_ONLY])

    # -- execution (prefill-style single-shot inference) ---------------------
    def head(self, batch, option: str):
        cfg = self.cfg
        if option == UE_ONLY:
            h = T.embed_inputs(cfg, self.params, batch)
            B, S = h.shape[:2]
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            h, _, _ = T.forward_slice(cfg, self.params, h, pos, 0, cfg.n_layers)
            return None, self._finish(self.params, h)
        if option == SERVER_ONLY:
            return dict(batch), None
        l = int(option.removeprefix("split"))
        h = T.embed_inputs(cfg, self.params, batch)
        B, S = h.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h, _, _ = T.forward_slice(cfg, self.params, h, pos, 0, l)
        return {"h": h}, None

    def _tail_impl(self, params, payload, option: str):
        cfg = self.cfg
        if option == SERVER_ONLY:
            h = T.embed_inputs(cfg, params, payload)
            B, S = h.shape[:2]
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            h, _, _ = T.forward_slice(cfg, params, h, pos, 0, cfg.n_layers)
            return self._finish(params, h)
        l = int(option.removeprefix("split"))
        h = payload["h"]
        B, S = h.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h, _, _ = T.forward_slice(cfg, params, h, pos, l, cfg.n_layers)
        return self._finish(params, h)

    def _finish(self, params, h):
        from repro.models.layers import rms_norm
        h = rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        return T.unembed(self.cfg, params, h[:, -1:])

    # -- accounting ----------------------------------------------------------
    def _layer_flops(self) -> float:
        from repro.configs.base import count_active_params
        # 6ND per token per full model -> 2ND forward; per layer share
        n_active = count_active_params(self.cfg)
        return 2.0 * n_active / self.cfg.n_layers

    def head_flops(self, option: str) -> float:
        if option == UE_ONLY:
            return (self._layer_flops() * self.cfg.n_layers
                    * self.workload.n_tokens)
        if option == SERVER_ONLY:
            return 0.0
        l = int(option.removeprefix("split"))
        return self._layer_flops() * l * self.workload.n_tokens

    def tail_flops(self, option: str) -> float:
        total = (self._layer_flops() * self.cfg.n_layers
                 * self.workload.n_tokens)
        return total - self.head_flops(option)

    def payload_specs(self, option: str) -> List[Tuple[Tuple[int, ...], str]]:
        cfg = self.cfg
        seq_len = self.workload.n_tokens
        if option == UE_ONLY:
            return []
        if option == SERVER_ONLY:
            return [((seq_len,), "int32")]
        specs = [((seq_len, cfg.d_model), cfg.dtype)]
        if self.workload.include_state and cfg.family in ("ssm", "hybrid"):
            l = int(option.removeprefix("split"))
            # recurrent state of head-side layers ships on split move
            di = cfg.ssm_expand * cfg.d_model
            if cfg.family == "ssm":
                hd = di // cfg.n_heads
                specs.append(((l, cfg.n_heads, hd, hd), "float32"))   # mLSTM C
            else:
                specs.append(((l, di, cfg.ssm_state), "float32"))     # mamba h
        return specs
