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
class Latent:
    """Latent attention's widths (MLA without a query bottleneck): keys
    and values are expanded from a ``rank``-wide normalised latent, a
    score is ``nope + rope`` wide of which the last ``rope`` carry the
    rotary (one rotary key for all heads), a value ``v`` wide."""
    rank: int
    nope: int
    rope: int
    v: int


@dataclasses.dataclass(frozen=True)
class Router:
    """How a routed layer scores and chooses: ``score`` over all experts
    (``softmax`` | ``sigmoid``), the top k taken on ``score + bias`` where
    ``select_bias`` (the weights stay the scores without it), the chosen
    weights over ``their sum + eps`` and times ``scale``.  ``bias_std``:
    the scale the bias is drawn at.  It has no gradient; ``bias_step`` is
    the speed of the rule that moves it once an optimizer step, from the
    step's count ``n_e`` of the pairs that chose each expert (DeepSeek-V3's
    balancing without an auxiliary loss, ``train.move_select_bias``): ``d_e
    = bias_step * sign(mean(n) - n_e)``, ``b_e += d_e - mean(d)``.  At 0
    no rule moves it, and what it is drawn as is what it stays."""
    score: str = "softmax"
    select_bias: bool = False
    scale: float = 1.0
    eps: float = 0.0
    bias_std: float = 0.0
    bias_step: float = 0.0


@dataclasses.dataclass(frozen=True)
class DecoderArch:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int                  # given, not hidden // heads; the scores'
    layer_types: tuple             # one period: "sliding" | "full" each
    periods: int                   # periods kept (the cut in depth)
    window: int                    # of the "sliding" layers; counts itself
    rope: tuple                    # ((layer type, Rope), ...); a kind
    #                                that is not listed has no rotary
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
    latent: Optional[Latent] = None    # None: grouped-query attention
    rope_interleaved: bool = False     # rotary on pairs (2n, 2n + 1)
    # layers BEFORE the periods whose MLP is one dense SwiGLU: (layers,
    # width); of another parameter shape, so outside the scan
    lead_dense: tuple = (0, 0)
    shared_ffn: int = 0            # SwiGLU width every token passes beside
    #                                its routed experts (0: none)
    router: Router = Router()
    qk_norm: bool = False          # RMSNorm over each head's q and k, one
    #                                learned head_dim-wide scale each
    attn_gate: bool = False        # sigmoid(x Wg) times attention's output,
    #                                a head at a time, before the projection
    sandwich_norm: bool = False    # a norm on each sublayer's OUTPUT too,
    #                                before the residual add: four a block
    embed_scale: float = 1.0       # the embedding's rows times this (muP:
    #                                sqrt(hidden))

    @property
    def layers(self) -> int:
        return self.lead_dense[0] + self.periods * len(self.layer_types)

    def rope_of(self, layer_type: str) -> Optional[Rope]:
        return dict(self.rope).get(layer_type)

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
        # a manifest written before a field existed takes its default
        if d.get("latent") is not None:
            d["latent"] = Latent(**d["latent"])
        if "router" in d:
            d["router"] = Router(**d["router"])
        records = ("rope", "latent", "router")
        return cls(**{k: v if k in records else tup(v)
                      for k, v in d.items()})


_PERIOD = ("sliding", "sliding", "sliding", "full")
# the scale a selection bias is drawn at (a recipe, not an equation: the
# source gives neither the trained bias nor the rule that moved it): large
# enough to change choices, so that a program which adds it to the weights
# or drops it computes something else, small enough to leave the load
# where the scores put it.  At the published widths (unit-RMS rows, router
# at std 0.02: logits of std 0.9, 6 of 128) a bias at 0.005 changes 3.8% of
# the choices, 0.002 1.9%, 0.01 8.0%, and the fullest held expert reads
# 1.13 of the mean against 1.12 without it (numpy, PERF.md section 4)
ROUTER_BIAS_STD = 0.005

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
    # kakaocorp/kanana-2-30b-a3b-instruct-2601, config.json (model_type
    # deepseek_v3, q_lora_rank null): 48 layers, the first with a dense
    # MLP of width 6144, then 47 of 128 routed experts (6 a token, sigmoid
    # scores, a selection bias, weights renormalised and times 2.448) plus
    # two shared experts; latent attention, 32 heads, scores 128 + 64
    # wide, values 128, latent 512; vocabulary 128,256.  Held here, as one
    # of the eight chips that share each layer of a deployment: the dense
    # layer and the four after it, experts 0-15 of every sparse layer, rows
    # 0-16,031 of embedding and head; the other 43 layers lie on further
    # chips (benchmarks/configs/kanana2_30b_a3b.json, PERF.md 4)
    "kanana2_30b_a3b": DecoderArch(
        hidden=2048, heads=32, kv_heads=32, head_dim=192,
        layer_types=("full",), periods=4, window=0,
        rope=(("full", Rope(1000000.0)),), norm_eps=1e-6, vocab=16032,
        experts=128, experts_per_token=6, expert_ffn=768,
        experts_held=(0, 16),
        published=(("layers", 48), ("experts", 128), ("vocab", 128256)),
        latent=Latent(rank=512, nope=128, rope=64, v=128),
        rope_interleaved=True, lead_dense=(1, 6144), shared_ffn=1536,
        router=Router("sigmoid", True, 2.448, 1e-20, ROUTER_BIAS_STD)),
    # the CPU tests' preset of the same block: one dense and two sparse
    # layers, every expert held
    "kanana2_tiny": DecoderArch(
        hidden=64, heads=4, kv_heads=4, head_dim=24,
        layer_types=("full",), periods=2, window=0,
        rope=(("full", Rope(10000.0)),), norm_eps=1e-6, vocab=1000,
        experts=8, experts_per_token=2, expert_ffn=32, experts_held=(0, 8),
        latent=Latent(rank=32, nope=16, rope=8, v=16),
        rope_interleaved=True, lead_dense=(1, 128), shared_ffn=64,
        router=Router("sigmoid", True, 2.448, 1e-20, ROUTER_BIAS_STD)),
    # arcee-ai/Trinity-Mini, config.json (model_type afmoe): 32 layers =
    # 8 periods of (3 sliding, 1 full), window 2048, rotary on the sliding
    # layers alone; attention with RMSNorm on every head's q and k and a
    # sigmoid gate on its output; a norm before AND after each sublayer;
    # the embedding times sqrt(2048) (mup_enabled); layers 0-1 a dense MLP
    # of 6144, then 128 routed experts (8 a token, sigmoid scores, weights
    # renormalised and times 2.826) plus one shared expert, and a selection
    # bias that a rule moves every step (load_balance_coeff 0.001);
    # vocabulary 200,192.  Held here, as one of the sixteen chips that share
    # each layer of a deployment: layer 0 (the two leading dense layers
    # count once) and one period of sparse layers, experts 0-7 of every
    # sparse layer, rows 0-25,023 of embedding and head (the vocabulary
    # eight ways, each eighth on two chips); the other layers lie on
    # further chips (benchmarks/configs/trinity_mini_26b_a3b.json, PERF.md 4)
    "trinity_mini_26b_a3b": DecoderArch(
        hidden=2048, heads=32, kv_heads=4, head_dim=128,
        layer_types=_PERIOD, periods=1, window=2048,
        rope=(("sliding", Rope(10000.0)),), norm_eps=1e-5, vocab=25024,
        experts=128, experts_per_token=8, expert_ffn=1024,
        experts_held=(0, 8),
        published=(("layers", 32), ("dense_layers", 2), ("experts", 128),
                   ("vocab", 200192)),
        # the embedding's rows at 0.25, times sqrt(2048) = 11.3 RMS beside
        # sublayer outputs that the block's second and fourth norm hold at 1:
        # the 11 : 1 of the two configurations above, for their reason.  At
        # 0.02 (0.9 RMS) the share on the held experts read 0.64 to 1.93 of
        # 8 / 128 by layer and seed and the fullest expert 7 to 11 times the
        # mean; at 0.25 0.94 to 1.12 and 1.7 to 2.1 (PERF.md section 6, PR 32)
        embed_std=0.25, lead_dense=(1, 6144), shared_ffn=1024,
        router=Router("sigmoid", True, 2.826, 1e-20, ROUTER_BIAS_STD,
                      bias_step=0.001),
        qk_norm=True, attn_gate=True, sandwich_norm=True,
        embed_scale=2048 ** 0.5),
    # the CPU tests' preset of the same block: one dense layer and two
    # periods, every expert held
    "trinity_tiny": DecoderArch(
        hidden=64, heads=4, kv_heads=2, head_dim=32,
        layer_types=_PERIOD, periods=2, window=16,
        rope=(("sliding", Rope(10000.0)),), norm_eps=1e-5, vocab=1000,
        experts=8, experts_per_token=2, expert_ffn=32, experts_held=(0, 8),
        embed_std=0.02, lead_dense=(1, 128), shared_ffn=32,
        router=Router("sigmoid", True, 2.826, 1e-20, ROUTER_BIAS_STD,
                      bias_step=0.001),
        qk_norm=True, attn_gate=True, sandwich_norm=True,
        embed_scale=64 ** 0.5),
}
