"""Uniform model interface over all families."""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

from repro.models import encdec as _encdec
from repro.models import lm as _lm
from repro.models.common import ModelConfig


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable              # key -> params
    forward: Callable           # (params, batch) -> (logits, aux)
    loss: Callable              # (params, batch) -> (loss, metrics)
    prefill: Callable           # (params, batch, max_len) -> (logits, cache)
    decode_step: Callable       # (params, cache, tokens) -> (logits, cache)
    init_cache: Callable        # (batch, max_len, **kw) -> cache pytree
    # state the step updates outside the optimizer (lm.init_buffers): None,
    # or a pytree that loss/prefill/decode_step take as a last argument
    init_buffers: Callable = lambda: None
    update_buffers: Optional[Callable] = None   # (buffers, load) -> buffers


def build(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec":
        return Model(
            cfg=cfg,
            init=lambda key: _encdec.init_encdec(cfg, key),
            forward=lambda p, b: _encdec.forward(cfg, p, b["frames"], b["dec_tokens"]),
            loss=lambda p, b: _encdec.loss_fn(cfg, p, b),
            prefill=lambda p, b, max_len: _encdec.prefill(
                cfg, p, b["frames"], b["dec_tokens"], max_len),
            decode_step=lambda p, c, t: _encdec.decode_step(cfg, p, c, t),
            init_cache=lambda batch, max_len, enc_len=1500: _encdec.init_cache(
                cfg, batch, max_len, enc_len),
        )
    return Model(
        cfg=cfg,
        init=lambda key: _lm.init_lm(cfg, key),
        forward=lambda p, b: _lm.forward(cfg, p, b["tokens"], b.get("positions")),
        loss=lambda p, b, buffers=None: _lm.loss_fn(cfg, p, b, buffers),
        prefill=lambda p, b, max_len, buffers=None: _lm.prefill(
            cfg, p, b["tokens"], max_len, b.get("positions"), buffers),
        decode_step=lambda p, c, t, buffers=None: _lm.decode_step(cfg, p, c, t, buffers),
        init_cache=lambda batch, max_len, **_kw: _lm.init_cache(cfg, batch, max_len),
        init_buffers=lambda: _lm.init_buffers(cfg),
        update_buffers=lambda buf, load: _lm.update_buffers(cfg, buf, load),
    )
