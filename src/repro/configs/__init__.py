"""Architecture registry: one module per assigned arch (exact published
config), the four assigned input shapes, and ShapeDtypeStruct input specs for
the allocation-free dry-run.

Every arch exposes:
  * ``CONFIG``      — the full :class:`repro.models.transformer.ModelConfig`.
  * ``smoke_config()`` — a reduced same-family config for CPU smoke tests.
  * applicability flags (which shapes run; long_500k only for sub-quadratic
    families — see DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.transformer import DecodeState, ModelConfig, TransformerLM


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, Shape] = {
    "train_4k": Shape("train_4k", 4_096, 256, "train"),
    "prefill_32k": Shape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": Shape("decode_32k", 32_768, 128, "decode"),
    "long_500k": Shape("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS: List[str] = [
    "hymba-1.5b",
    "stablelm-12b",
    "qwen2.5-3b",
    "h2o-danube-1.8b",
    "gemma2-27b",
    "internvl2-2b",
    "whisper-large-v3",
    "dbrx-132b",
    "llama4-maverick-400b-a17b",
    "mamba2-2.7b",
    # the paper's own LLM-serving case-study model (§6, LLaMA 3.1 8B class):
    "llama31-8b",
    "granite-4.0-h-small",
]

_MODULES = {
    "hymba-1.5b": "hymba_1p5b",
    "stablelm-12b": "stablelm_12b",
    "qwen2.5-3b": "qwen25_3b",
    "h2o-danube-1.8b": "h2o_danube_1p8b",
    "gemma2-27b": "gemma2_27b",
    "internvl2-2b": "internvl2_2b",
    "whisper-large-v3": "whisper_large_v3",
    "dbrx-132b": "dbrx_132b",
    "llama4-maverick-400b-a17b": "llama4_maverick",
    "mamba2-2.7b": "mamba2_2p7b",
    "llama31-8b": "llama31_8b",
    "granite-4.0-h-small": "granite_4h_small",
}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    arch_id: str
    config: ModelConfig
    smoke: ModelConfig
    #: sub-quadratic decode state (SSM / SWA / local-global) => long_500k runs
    long_context: bool
    notes: str = ""

    def shapes(self) -> List[Shape]:
        out = [SHAPES["train_4k"], SHAPES["prefill_32k"], SHAPES["decode_32k"]]
        if self.long_context:
            out.append(SHAPES["long_500k"])
        return out

    def shape_applicable(self, shape_name: str) -> bool:
        if shape_name == "long_500k":
            return self.long_context
        return shape_name in SHAPES


def get_arch(arch_id: str) -> ArchSpec:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.SPEC


def all_archs() -> List[ArchSpec]:
    return [get_arch(a) for a in ARCH_IDS]


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input — weak-type
# correct, shardable, zero allocation (MULTI-POD DRY-RUN step 2).
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(
    spec: ArchSpec, shape: Shape, *, batch_override: Optional[int] = None
) -> Dict[str, Any]:
    """Returns kwargs-of-specs for the step function of ``shape.kind``.

    train:   {"tokens": [B,S] i32, "labels": [B,S] i32, (+"frontend_embeds")}
    prefill: {"tokens": [B,S] i32, (+"frontend_embeds")}
    decode:  {"token": [B] i32, "state": DecodeState specs}
    """
    cfg = spec.config
    b = batch_override or shape.global_batch
    s = shape.seq_len
    out: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        out["tokens"] = _sds((b, s), jnp.int32)
        if shape.kind == "train":
            out["labels"] = _sds((b, s), jnp.int32)
        if cfg.frontend == "vision":
            out["frontend_embeds"] = _sds(
                (b, cfg.frontend_seq, cfg.d_model), jnp.float32
            )
        elif cfg.frontend == "audio":
            out["frontend_embeds"] = _sds(
                (b, cfg.encoder_seq, cfg.d_model), jnp.float32
            )
    elif shape.kind == "decode":
        out["token"] = _sds((b,), jnp.int32)
        model = TransformerLM(cfg)
        out["state"] = jax.eval_shape(
            lambda: model.init_decode_state(b, s)
        )
    else:
        raise ValueError(shape.kind)
    return out
