"""Decoder-only language models as data (ROADMAP D6 starts here).

A ``DecoderArch`` says what one block is made of and how the blocks
repeat; ``models/decoder.py`` builds the one block definition from it,
and everything else that has to know the architecture reads the same
record: the scan over periods, the predicates and the remat vocabulary in
``models/__init__.py``, and the checkpoint manifest
(``driver.checkpoint_metadata`` writes ``as_manifest``,
``from_manifest`` rebuilds).  A new model of this kind is a new record
here, not a new ``if name ==`` arm.

The cut of a model to one chip's share of a deployment (the
``model-configs`` guide, section 4) is three numbers beside the published
ones: the layers kept, the experts held and the rows of the vocabulary
held.  Widths, the router's width and the experts a token are never cut.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary parameters of one kind of layer; ``yarn`` is ``(factor,
    original_max_position, beta_fast, beta_slow, attention_factor)``
    (``ops.attention.rope_frequencies``)."""
    theta: float
    yarn: Optional[tuple] = None


@dataclasses.dataclass(frozen=True)
class DecoderArch:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int                  # given, not hidden // heads
    layer_types: tuple             # one period: "sliding" | "full" each
    periods: int                   # periods kept (the cut in depth)
    window: int                    # of the "sliding" layers; counts itself
    rope: tuple                    # ((layer type, Rope), ...)
    norm_eps: float
    vocab: int                     # rows of embedding and head HELD
    experts: int                   # the router's width: all experts
    experts_per_token: int
    expert_ffn: int                # SwiGLU width of one expert
    experts_held: tuple            # (first, count) held here
    published: tuple = ()          # (("layers", n), ("experts", n), ("vocab", n))
    # the embedding's rows are drawn at this scale, every other matrix at
    # 0.02.  At 0.02 a layer's attention output (~0.09 an element at
    # initialisation) drowns the token's own row (~0.016): neighbours hand
    # the router the same vector, a few experts take most tokens, and the
    # share that lands on the experts held swings from 0.21 to 0.48 with
    # the seed (PERF.md section 6, PR 26).  At 1 it is 0.25 +- 0.01
    embed_std: float = 1.0

    @property
    def layers(self) -> int:
        return self.periods * len(self.layer_types)

    def rope_of(self, layer_type: str) -> Rope:
        return dict(self.rope)[layer_type]

    def window_of(self, layer_type: str) -> Optional[int]:
        return self.window if layer_type == "sliding" else None

    def as_manifest(self) -> dict:
        """JSON-able, for MANIFEST.json."""
        return dataclasses.asdict(self)

    @classmethod
    def from_manifest(cls, d: dict) -> "DecoderArch":
        tup = lambda x: tuple(tup(y) for y in x) if isinstance(
            x, (list, tuple)) else x
        d = dict(d)
        d["rope"] = tuple(
            (kind, Rope(r["theta"], None if r["yarn"] is None
                        else tuple(r["yarn"]))) for kind, r in d["rope"])
        return cls(**{k: tup(v) if k != "rope" else v for k, v in d.items()})


_PERIOD = ("sliding", "sliding", "sliding", "full")

ARCHS = {
    # JetBrains/Mellum2-12B-A2.5B-Instruct, config.json (model_type
    # mellum): 28 layers = 7 periods of (3 sliding, 1 full), 64 experts,
    # vocabulary 98,304.  Held here, as one of the four chips that share
    # each layer of a deployment: one period, experts 0-15 of every layer,
    # rows 0-24,575 of embedding and head; the other 24 layers lie on
    # further chips (benchmarks/configs/mellum2_12b_a2p5b.json, PERF.md 4)
    "mellum2_12b_a2p5b": DecoderArch(
        hidden=2304, heads=32, kv_heads=4, head_dim=128,
        layer_types=_PERIOD, periods=1, window=1024,
        rope=(("sliding", Rope(500000.0)),
              ("full", Rope(500000.0, (16.0, 8192, 32.0, 1.0,
                                       1.2772588722239782)))),
        norm_eps=1e-6, vocab=24576, experts=64, experts_per_token=8,
        expert_ffn=896, experts_held=(0, 16),
        published=(("layers", 28), ("experts", 64), ("vocab", 98304))),
    # the CPU tests' preset of the same block: two periods, every expert
    # held, synthetic_lm's vocabulary
    "mellum2_tiny": DecoderArch(
        hidden=64, heads=4, kv_heads=2, head_dim=32,
        layer_types=_PERIOD, periods=2, window=16,
        rope=(("sliding", Rope(10000.0)),
              ("full", Rope(10000.0, (16.0, 32, 32.0, 1.0,
                                      1.2772588722239782)))),
        norm_eps=1e-6, vocab=1000, experts=8, experts_per_token=2,
        expert_ffn=32, experts_held=(0, 8)),
}
