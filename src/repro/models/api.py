"""Unified model facade.

``build_model(cfg)`` returns a ``Model`` whose members are pure functions —
the single entry point used by the trainer, the server, the dry-run, the NAS
supernet and the AMC/HAQ environments.

The ``dot`` hook threads HAQ quantization through every matmul: it receives
(activations, weights, site_name) and may dispatch to the Pallas quantized
kernel per the active bitwidth policy.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models import params as plib

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    defs: Any

    # -- parameters ---------------------------------------------------------
    def init(self, key) -> Any:
        return plib.init_params(self.defs, key)

    def abstract_params(self) -> Any:
        return plib.abstract_params(self.defs)

    def logical_specs(self) -> Any:
        return plib.logical_specs(self.defs)

    def param_count(self) -> int:
        return plib.param_count(self.defs)

    def param_bytes(self) -> int:
        return plib.param_bytes(self.defs)

    # -- compute ------------------------------------------------------------
    def forward(self, params, batch, *, want_cache=False, remat=False,
                ac=None, dot=None, unembed_mode="full",
                cache_layout="ring"):
        ac = ac or transformer._identity_ac
        if self.cfg.is_encdec:
            return encdec.forward(params, batch, self.cfg,
                                  want_cache=want_cache, remat=remat, ac=ac,
                                  dot=dot, unembed_mode=unembed_mode)
        return transformer.forward(params, batch, self.cfg,
                                   want_cache=want_cache, remat=remat, ac=ac,
                                   dot=dot, unembed_mode=unembed_mode,
                                   cache_layout=cache_layout)

    def loss(self, params, batch, *, remat=False, ac=None, dot=None):
        hidden, _, aux, fmask = self.forward(params, batch, want_cache=False,
                                             remat=remat, ac=ac, dot=dot,
                                             unembed_mode="none")
        labels = batch["labels"]
        if fmask is not None:  # vlm: loss only over the text segment
            S_txt = labels.shape[1]
            hidden = hidden[:, -S_txt:]
        ce = transformer.chunked_ce(params, hidden, labels, self.cfg, dot=dot)
        return ce + 0.01 * aux

    def prefill(self, params, batch, *, ac=None, dot=None,
                cache_layout="ring", unembed_mode="last"):
        logits, cache, _, _ = self.forward(params, batch, want_cache=True,
                                           ac=ac, dot=dot,
                                           unembed_mode=unembed_mode,
                                           cache_layout=cache_layout)
        return logits, cache

    def decode_step(self, params, cache, token, pos, *, ac=None, dot=None):
        step = encdec.decode_step if self.cfg.is_encdec \
            else transformer.decode_step
        ac = ac or transformer._identity_ac
        return step(params, cache, token, pos, self.cfg, ac=ac, dot=dot)

    def unembed(self, params, hidden, *, dot=None):
        """Project hidden states (B, S, D) to logits (decoder-only)."""
        return transformer.unembed(params, hidden, self.cfg, dot=dot)

    def decode_step_paged(self, params, pool, page_table, token, positions,
                          *, rows=None, dot=None, kernel="auto"):
        """Continuous-batching decode: per-sequence positions, KV walked
        page-by-page through the page table (see serving/engine). ``kernel``
        picks the paged-attention path: "auto" (Pallas on TPU, pure-JAX
        block walk elsewhere), "pallas", or "ref". ``rows``: each batch
        row's recurrent-state slot, for models with Mamba layers."""
        return transformer.decode_step_paged(params, pool, page_table, token,
                                             positions, self.cfg, rows=rows,
                                             dot=dot, kernel=kernel)

    def prefill_chunk_paged(self, params, pool, page_table, tokens,
                            positions, *, rows=None, lengths=None, dot=None,
                            kernel="auto"):
        """Chunked prefill: run one prompt chunk (tokens (B, Sq), first
        token of sequence b at absolute position ``positions[b]``) through
        the model, scattering its K/V into the paged pool and attending
        over the pool itself (resident prefix + chunk). Returns
        (hidden (B, Sq, D), new_pool); unembed the rows you need via
        ``unembed``. ``rows`` and ``lengths``: each sequence's recurrent-
        state slot and real tokens in the chunk, for models with Mamba
        layers. See transformer.prefill_chunk_paged."""
        return transformer.prefill_chunk_paged(params, pool, page_table,
                                               tokens, positions, self.cfg,
                                               rows=rows, lengths=lengths,
                                               dot=dot, kernel=kernel)

    # -- caches & inputs ----------------------------------------------------
    def cache_specs(self, batch: int, seq_len: int):
        fn = encdec.cache_specs if self.cfg.is_encdec \
            else transformer.cache_specs
        return fn(self.cfg, batch, seq_len)

    def init_cache(self, batch: int, seq_len: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(batch, seq_len))

    def pool_specs(self, num_pages: int, page_size: int, kv_bits=None,
                   state_slots: int = 0):
        """``kv_bits`` selects the HAQ KV-quantized pool layout (int8/int4
        pages + per-page-slot scales) per sub-layer slot; None keeps the
        bf16 pool. ``state_slots``: rows of recurrent state beside the
        pages, for models with Mamba layers. See transformer.pool_specs /
        serving/kvquant."""
        return transformer.pool_specs(self.cfg, num_pages, page_size,
                                      kv_bits=kv_bits,
                                      state_slots=state_slots)

    def init_pool(self, num_pages: int, page_size: int, kv_bits=None,
                  state_slots: int = 0):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.pool_specs(num_pages, page_size, kv_bits,
                                            state_slots))

    def input_specs(self, shape) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for one step's inputs (dry-run)."""
        B, S = shape.global_batch, shape.seq_len
        cfg = self.cfg
        if shape.kind == "decode":
            return {
                "cache": self.cache_specs(B, S),
                "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "pos": jax.ShapeDtypeStruct((), jnp.int32),
            }
        batch: Dict[str, Any] = {}
        if cfg.is_encdec:
            batch["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                   jnp.bfloat16)
            Sd = max(S // cfg.dec_ratio, 2)
            batch["tokens"] = jax.ShapeDtypeStruct((B, Sd), jnp.int32)
            if shape.kind == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, Sd), jnp.int32)
        elif cfg.frontend == "vision_stub":
            Sp = int(S * cfg.patch_frac)
            batch["patches"] = jax.ShapeDtypeStruct((B, Sp, cfg.d_model),
                                                    jnp.bfloat16)
            batch["tokens"] = jax.ShapeDtypeStruct((B, S - Sp), jnp.int32)
            if shape.kind == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, S - Sp), jnp.int32)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
            if shape.kind == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        return batch

    def batch_logical_specs(self, shape) -> Dict[str, Any]:
        """Logical axes for the input batch (mirrors input_specs)."""
        if shape.kind == "decode":
            fn = encdec.cache_axes if self.cfg.is_encdec \
                else transformer.cache_axes
            return {"cache": fn(self.cfg),
                    "token": ("batch", "seq"),
                    "pos": ()}
        axes: Dict[str, Any] = {}
        cfg = self.cfg
        if cfg.is_encdec:
            axes["frames"] = ("batch", "seq", "embed_act")
            axes["tokens"] = ("batch", "seq")
            axes["labels"] = ("batch", "seq")
        elif cfg.frontend == "vision_stub":
            axes["patches"] = ("batch", "seq", "embed_act")
            axes["tokens"] = ("batch", "seq")
            axes["labels"] = ("batch", "seq")
        else:
            axes["tokens"] = ("batch", "seq")
            axes["labels"] = ("batch", "seq")
        return {k: v for k, v in axes.items()
                if k in self.input_specs(shape)}


def build_model(cfg) -> Model:
    defs = encdec.param_defs(cfg) if cfg.is_encdec \
        else transformer.param_defs(cfg)
    return Model(cfg=cfg, defs=defs)
