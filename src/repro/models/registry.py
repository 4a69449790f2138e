"""Model registry: one API over all architecture families.

``build(cfg)`` returns a ``ModelAPI`` whose five functions are everything the
trainer, server, benchmarks and dry-run ever call.  ``input_specs`` produces
ShapeDtypeStruct stand-ins for any assigned ShapeConfig — the dry-run lowers
against these without allocating anything.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import linear, transformer, whisper, xlstm, zamba2


def _scoped(cfg: ModelConfig, fn):
    """Apply cfg-level precision scope around a model function."""
    if not cfg.bf16_reduce:
        return fn

    def wrapped(*a, **kw):
        with linear.reduce_precision_scope(True):
            return fn(*a, **kw)
    return wrapped


@dataclasses.dataclass(frozen=True)
class FamilyCaps:
    """Per-family capability record — the serving engine's one source of
    truth for what a family's decode state looks like (docs/DIST.md).

    The slot pool consults this record instead of pattern-matching on
    ``cfg.family``: every registered family gets one, and a family whose
    API lacks it is refused by ``SlotPool`` (no silent garbage tracing).

      * ``positional`` — decode threads an absolute position through the
        cache (attention KV rows).  False for pure recurrent state (SSM),
        whose ``decode_step`` ignores ``pos`` entirely.
      * ``prefix_key`` — batch key for per-request prefix state admitted
        once per slot (``"image_embeds"`` for vlm patch embeddings,
        ``"frames"`` for encdec encoder inputs); ``None`` = no prefix.
      * ``prefix_required`` — prefill raises without the prefix (encdec:
        there is nothing to cross-attend); vlm prefixes are optional.
      * ``prefix_positions`` — the prefix occupies decoder cache
        positions (vlm: patch rows share the causal sequence).  Encdec
        cross-KV lives in its own position-free leaves, so frames consume
        ZERO decoder slots.
      * ``bucketable`` — prompt-length bucketing (right-pad + masked
        last-position gather) is sound: padded rows must stay causally
        invisible, which rules out recurrent state (it integrates every
        input) and is additionally gated on no sliding-window ring.
      * ``slotted_reason`` — why ``decode_step_slotted`` is None (the
        resident scheduler's refusal message); None = supported.
      * ``mesh_slotted_reason`` — why the slotted steps, present, cannot
        run under a mesh (the refusal message there); None = they can.
    """
    positional: bool = True
    prefix_key: Optional[str] = None
    prefix_required: bool = False
    prefix_positions: bool = False
    bucketable: bool = False
    slotted_reason: Optional[str] = None
    mesh_slotted_reason: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable            # (rng) -> params
    loss_fn: Callable         # (params, batch) -> scalar
    prefill: Callable         # (params, batch) -> (last_logits, cache)
    decode_step: Callable     # (params, cache, tokens, pos) -> (logits, cache)
    init_cache: Callable      # (batch, seq_len) -> cache pytree
    # (params, task_stack, cache, tokens, pos, task_ids) -> (logits, cache)
    # mixed-task decode against (T, …)-stacked scales, with the step's MoE
    # routing stats (4,) int32 after them for a MoE family; None for
    # families that cannot thread per-slot scales (SSM, encdec)
    decode_step_slotted: Optional[Callable] = None
    # (params, task_stack, batch, task_ids) -> (last_logits, cache): prefill
    # reading per-row scales from the resident stack (no live-scale swap at
    # admit); gated exactly like decode_step_slotted
    prefill_slotted: Optional[Callable] = None
    # what the serving engine may assume about this family's decode state;
    # ``build`` always sets it — None only on hand-rolled stand-ins, which
    # the slot pool refuses
    caps: Optional[FamilyCaps] = None

    def input_specs(self, shape: ShapeConfig) -> dict:
        return input_specs(self.cfg, shape)

    def cache_specs(self, shape: ShapeConfig) -> dict:
        return jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len))


def _tok_spec(b, s):
    return jax.ShapeDtypeStruct((b, s), jnp.int32)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Batch pytree of ShapeDtypeStruct for (cfg, shape)."""
    b, s = shape.global_batch, shape.seq_len
    dtype = jnp.dtype(cfg.dtype)
    if shape.kind == "decode":
        return {"tokens": _tok_spec(b, 1),
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    batch: dict = {}
    if cfg.family == "vlm":
        p = cfg.n_img_tokens
        batch["image_embeds"] = jax.ShapeDtypeStruct((b, p, cfg.d_model), dtype)
        batch["tokens"] = _tok_spec(b, s - p)
        batch["labels"] = _tok_spec(b, s - p)
    elif cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model), dtype)
        batch["tokens"] = _tok_spec(b, s)
        batch["labels"] = _tok_spec(b, s)
    else:
        batch["tokens"] = _tok_spec(b, s)
        batch["labels"] = _tok_spec(b, s)
    if shape.kind == "prefill":
        batch.pop("labels", None)
    return batch


_NO_SLOTTED = ("recurrent state layers cannot thread per-slot scales "
               "(no slotted decode step)")


def build(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        def prefill_fn(params, batch):
            return transformer.prefill(params, batch["tokens"], cfg,
                                       prefix_embeds=batch.get("image_embeds"),
                                       last_pos=batch.get("last_pos"))

        moe_cfg = cfg.moe is not None
        caps = FamilyCaps(
            positional=True, bucketable=True,
            prefix_key="image_embeds" if fam == "vlm" else None,
            prefix_positions=fam == "vlm",
            mesh_slotted_reason=("MoE experts read per-slot task scales "
                                 "off the mesh only") if moe_cfg else None)

        def slotted_step(p, st, c, t, pos, tid):
            # a MoE step also returns its routing stats, which the engine
            # reads with the step's tokens
            logits, cache, stats = transformer.decode_tokens(
                p, c, t, pos, cfg, task_stack=st, task_ids=tid)
            out = (logits[:, 0], cache)
            return out + (stats,) if moe_cfg else out

        return ModelAPI(
            cfg=cfg,
            init=lambda rng: transformer.init(rng, cfg),
            loss_fn=_scoped(cfg, lambda p, b: transformer.loss_fn(p, b, cfg)),
            prefill=_scoped(cfg, prefill_fn),
            decode_step=_scoped(cfg, lambda p, c, t, pos: transformer.decode_step(p, c, t, pos, cfg)),
            init_cache=lambda b, s: transformer.init_cache(cfg, b, s),
            decode_step_slotted=_scoped(cfg, slotted_step),
            prefill_slotted=_scoped(
                cfg, lambda p, st, b, tid: transformer.prefill(
                    p, b["tokens"], cfg,
                    prefix_embeds=b.get("image_embeds"),
                    last_pos=b.get("last_pos"),
                    task_stack=st, task_ids=tid)),
            caps=caps,
        )
    if fam == "hybrid":
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: zamba2.init(rng, cfg),
            loss_fn=lambda p, b: zamba2.loss_fn(p, b, cfg),
            prefill=lambda p, b: zamba2.prefill(p, b["tokens"], cfg),
            decode_step=lambda p, c, t, pos: zamba2.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, s: zamba2.init_cache(cfg, b, s),
            caps=FamilyCaps(positional=True, bucketable=False,
                            slotted_reason=_NO_SLOTTED),
        )
    if fam == "ssm":
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: xlstm.init(rng, cfg),
            loss_fn=lambda p, b: xlstm.loss_fn(p, b, cfg),
            prefill=lambda p, b: xlstm.prefill(p, b["tokens"], cfg),
            decode_step=lambda p, c, t, pos: xlstm.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, s: xlstm.init_cache(cfg, b, s),
            caps=FamilyCaps(positional=False, bucketable=False,
                            slotted_reason=_NO_SLOTTED),
        )
    if fam == "encdec":
        return ModelAPI(
            cfg=cfg,
            init=lambda rng: whisper.init(rng, cfg),
            loss_fn=lambda p, b: whisper.loss_fn(p, b, cfg),
            prefill=lambda p, b: whisper.prefill(p, b["frames"], b["tokens"],
                                                 cfg,
                                                 last_pos=b.get("last_pos")),
            decode_step=lambda p, c, t, pos: whisper.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, s: whisper.init_cache(cfg, b, s),
            caps=FamilyCaps(positional=True, bucketable=True,
                            prefix_key="frames", prefix_required=True,
                            prefix_positions=False,
                            slotted_reason=("encoder-decoder backbone has "
                                            "no slotted decode step")),
        )
    raise ValueError(f"unknown family {fam}")
