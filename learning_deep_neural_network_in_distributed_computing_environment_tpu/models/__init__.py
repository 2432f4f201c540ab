"""Model zoo.

``enhanced_cnn`` is the reference's flagship (``Balanced All-Reduce/
model.py:52-111``).  The rest form the BASELINE.md config ladder:
mlp -> lenet5 -> resnet18 -> resnet50 -> bert_base.
"""

from __future__ import annotations

from typing import Any

from .arch import ARCHS


def get_model(name: str, **kw: Any):
    """Build a flax module by registry name (lazy imports keep startup cheap)."""
    name = name.lower()
    if name not in MODEL_INPUT_SPECS:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(MODEL_INPUT_SPECS)}")
    if name in ARCHS:
        from .decoder import DecoderLM
        return DecoderLM(arch=ARCHS[name], **kw)
    if name == "enhanced_cnn":
        from .cnn import EnhancedCNNModel
        return EnhancedCNNModel(**kw)
    if name == "mlp":
        from .mlp import MLP
        return MLP(**kw)
    if name == "lenet5":
        from .lenet import LeNet5
        return LeNet5(**kw)
    if name == "resnet18":
        from .resnet import ResNet18
        return ResNet18(**kw)
    if name == "resnet50":
        from .resnet import ResNet50
        return ResNet50(**kw)
    if name == "bert_base":
        from .bert import BertForMLM
        return BertForMLM(**kw)
    if name == "bert_tiny":
        # CPU-testable MLM model (same code path as bert_base, 2 layers)
        from .bert import BertForMLM
        kw.setdefault("num_layers", 2)
        kw.setdefault("hidden", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("ffn_dim", 128)
        return BertForMLM(**kw)
    if name == "gpt2_small":
        from .gpt import GPTForCausalLM
        return GPTForCausalLM(**kw)
    if name == "gpt_tiny":
        # CPU-testable causal LM (same code path as gpt2_small, 2 layers)
        from .gpt import GPTForCausalLM
        kw.setdefault("num_layers", 2)
        kw.setdefault("hidden", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("ffn_dim", 128)
        return GPTForCausalLM(**kw)
    if name == "gpt_small":
        # CPU-trainable middle size between gpt_tiny and gpt2_small —
        # the speculative-decoding TARGET of the draft/target smoke
        # (gpt_tiny drafts for it: same vocab, ~4x the per-step work)
        from .gpt import GPTForCausalLM
        kw.setdefault("num_layers", 4)
        kw.setdefault("hidden", 128)
        kw.setdefault("num_heads", 4)
        kw.setdefault("ffn_dim", 256)
        return GPTForCausalLM(**kw)
    if name == "llama_medium":
        from .llama import LlamaForCausalLM
        return LlamaForCausalLM(**kw)
    if name == "llama_tiny":
        # CPU-testable Llama (same code path as llama_medium, 2 layers)
        from .llama import LlamaForCausalLM
        kw.setdefault("num_layers", 2)
        kw.setdefault("hidden", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("ffn_dim", 176)
        return LlamaForCausalLM(**kw)
    if name == "vit_s16":
        from .vit import ViT
        return ViT(**kw)
    if name == "vit_b16":
        from .vit import ViT
        kw.setdefault("hidden", 768)
        kw.setdefault("num_heads", 12)
        kw.setdefault("ffn_dim", 3072)
        return ViT(**kw)
    if name == "vit_tiny":
        # CPU-testable ViT for 32x32 inputs (same code path as vit_s16)
        from .vit import ViT
        kw.setdefault("patch", 8)
        kw.setdefault("num_layers", 2)
        kw.setdefault("hidden", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("ffn_dim", 128)
        return ViT(**kw)
    raise ValueError(f"unknown model {name!r}")


def is_attention_model(name: str) -> bool:
    """True for transformer families (bert_*/gpt_*/vit_*/llama_*) — the
    models that accept attention/parallelism kwargs (TP, PP, MoE,
    attention_impl)."""
    name = name.lower()
    return name in ARCHS or name.startswith(("bert", "gpt", "vit", "llama"))


def supports_layer_scan(name: str) -> bool:
    """True for the homogeneous-block families whose repeated blocks can
    be stacked along a layer axis and run under ``lax.scan`` (the
    layer-scan compile engine): every transformer family.  CNN/MLP models
    have heterogeneous layers (changing widths/strides) that cannot
    share one stacked parameter block."""
    return is_attention_model(name)


def is_token_model(name: str) -> bool:
    """True for models whose input is a token-id sequence [B, L] — the
    shape sequence parallelism shards.  ViT is attention-based but takes
    images, so SP does not apply."""
    name = name.lower()
    return name in ARCHS or name.startswith(("bert", "gpt", "llama"))


# The named-activation vocabulary of the shared scanned-block path
# (ISSUE 15).  Every transformer family's block annotates EXACTLY these
# ``checkpoint_name`` labels — the stable contract the ``--remat_policy
# save_names:<set>`` / ``offload_names:<set>`` tiers select from, the
# eager config validation checks against, and graftlint's R6 rule
# discovers (a typo'd label silently degrades a named policy to
# save-NOTHING, which is why the vocabulary is closed):
#
# - ``attn_out``  — the attention sublayer's output projection
#   ([B, L, H] per block; the pjit/TPUv4 report's canonical save point);
# - ``mlp_out``   — the FFN / MoE sublayer output ([B, L, H]);
# - ``block_out`` — the block's residual-stream output (the layer
#   boundary — saving only these IS the GPipe-paper recipe, spelled as
#   a named set);
# - ``moe_dispatch`` — the tokens as the experts get them: the Switch
#   layer's expert-batched [E, C, H], the routed layer's rows in expert
#   order [M, H] (emitted only when the model has experts);
# - ``flash_out`` / ``flash_lse`` — what a flash kernel call produced
#   (``ops/pallas_ops.py::_flash_fwd_rule``): the attention output
#   [B, L, heads, dv] and the rows' log-sum-exp, float32 over a lane
#   tile [B, heads, L, 128].  Emitted only where the attention call
#   reaches the kernel.  In the tuple for graftlint R6; not in
#   ``remat_name_vocab``, because EVERY policy keeps them
#   (``KERNEL_RESIDUALS``) and a set that named them would change nothing.
REMAT_NAMES = ("attn_out", "mlp_out", "block_out", "moe_dispatch",
               "flash_out", "flash_lse")
# what every policy below keeps, whatever else it chooses
KERNEL_RESIDUALS = ("flash_out", "flash_lse")


def remat_name_vocab(name: str, num_experts: int = 0) -> tuple[str, ...]:
    """The ``checkpoint_name`` labels the ``name`` family's blocks emit
    — what a named remat policy may select from.  CNN/MLP families emit
    none (they have no scanned block path); ``moe_dispatch`` exists only
    when the run actually builds MoE FFNs."""
    if not is_attention_model(name):
        return ()
    base = ("attn_out", "mlp_out", "block_out")
    arch = ARCHS.get(name.lower())
    if num_experts > 0 or (arch is not None and arch.experts):
        return base + ("moe_dispatch",)
    return base


# Named rematerialization policies for the layer-scan engine (ISSUE 3).
# "everything" REMATERIALIZES every activation of XLA's (saves none of
# them: the historical ``remat=True`` behavior); "dots_saveable" saves
# matmul/einsum outputs and recomputes only the cheap elementwise chains
# between them — the pjit/TPUv4 scaling report's default selective-remat
# recipe.
#
# Every policy keeps ``KERNEL_RESIDUALS`` besides (ISSUE 35): a policy
# chooses among XLA's activations; a kernel call whose outputs are
# O(L d) and whose cost is O(L^2 d) is never rematerialised.  So
# "everything" is ``save_only_these_names(flash_out, flash_lse)``: where
# no attention call reaches the kernel (dense attention, a length the
# kernel does not tile, the CPU mesh inside ``shard_map``) nothing carries
# those names and it is ``nothing_saveable`` as before; where one does, a
# layer holds 2 B x heads x dv + 512 B a position more than it did (192
# MiB at 1 x 8192 x 32 heads of 128), and its backward pass runs the
# kernel's forward once, not twice.
#
# ISSUE 15 adds the NAMED-ACTIVATION tier: ``save_names:<a,b>`` keeps
# exactly the ``checkpoint_name``-annotated activations in the set on
# device (``save_only_these_names``), and ``offload_names:<a,b>``
# additionally moves them to host memory between forward and backward
# (``save_and_offload_only_these_names`` -> ``pinned_host``).  Both are
# pure residency policies: the math is the unannotated math, so every
# policy's fp32 trajectory is BITWISE the baseline's
# (tests/test_remat_memory.py).
REMAT_POLICIES = ("none", "dots_saveable", "everything")
NAMED_REMAT_KINDS = ("save_names", "offload_names")


def split_remat_policy(policy: str) -> tuple[str, tuple[str, ...]]:
    """``--remat_policy`` -> ``(kind, names)``: the three base spellings
    parse as ``(spelling, ())``; the named tiers as ``("save_names" |
    "offload_names", (name, ...))`` with duplicates collapsed.  Pure
    syntax — vocabulary validation against the model family lives in
    ``Config.parse_remat_policy`` (eager) so a typo'd name fails at
    argparse time with the family's emitted vocabulary in the message."""
    if ":" not in policy:
        if policy not in REMAT_POLICIES:
            raise ValueError(
                f"remat policy must be one of {REMAT_POLICIES} or "
                f"'save_names:<a,b>' / 'offload_names:<a,b>', got "
                f"{policy!r}")
        return policy, ()
    kind, _, names_csv = policy.partition(":")
    if kind not in NAMED_REMAT_KINDS:
        raise ValueError(
            f"named remat policy must start with one of "
            f"{NAMED_REMAT_KINDS}, got {policy!r}")
    names = tuple(dict.fromkeys(
        n.strip() for n in names_csv.split(",") if n.strip()))
    if not names:
        raise ValueError(
            f"--remat_policy {kind}: needs at least one activation name "
            f"(e.g. {kind}:attn_out), got {policy!r}")
    return kind, names


def host_offload_supported() -> bool:
    """True when the default backend can place offloaded-remat residuals
    in host memory: it exposes a ``pinned_host`` memory space.  A
    backend without one demotes offload — see ``checkpoint_policy``."""
    import jax
    return "pinned_host" in {
        m.kind for m in jax.devices()[0].addressable_memories()}


_OFFLOAD_DEMOTIONS_LOGGED: set[tuple[str, ...]] = set()


def checkpoint_policy(name):
    """Resolve a named ``--remat_policy`` to a ``jax.checkpoint`` policy
    callable.  ``name`` is one of ``REMAT_POLICIES`` minus "none" —
    callers gate the "none" (no remat at all) case themselves — or a
    named-activation spelling ``save_names:<a,b>`` /
    ``offload_names:<a,b>`` (ISSUE 15).  Whatever the spelling, what a
    flash kernel call produced (``KERNEL_RESIDUALS``) is saved besides.

    ``offload_names`` demotion: on a backend without a ``pinned_host``
    memory space (nowhere distinct to offload TO) the offload set
    demotes to the SAME-set ``save_names`` with a logged reason.
    Bitwise-safe by the remat contract: both policies save the
    identical values, only their residency differs, and residency never
    changes math."""
    import jax
    policies = jax.checkpoint_policies
    if ":" in name:
        kind, names = split_remat_policy(name)
        if kind == "offload_names":
            if host_offload_supported():
                return policies.save_and_offload_only_these_names(
                    names_which_can_be_saved=list(KERNEL_RESIDUALS),
                    names_which_can_be_offloaded=list(names),
                    offload_src="device", offload_dst="pinned_host")
            if names not in _OFFLOAD_DEMOTIONS_LOGGED:
                _OFFLOAD_DEMOTIONS_LOGGED.add(names)
                import logging
                logging.getLogger(__name__).info(
                    "remat policy offload_names:%s demoted to "
                    "save_names:%s — this backend (%s) has no "
                    "'pinned_host' memory space to offload to, so the "
                    "same-set device-saved policy is the "
                    "residency-equivalent; bitwise-identical math "
                    "either way",
                    ",".join(names), ",".join(names),
                    jax.default_backend())
        return policies.save_only_these_names(*names, *KERNEL_RESIDUALS)
    if name not in REMAT_POLICIES or name == "none":
        raise ValueError(
            f"remat policy must be one of {REMAT_POLICIES[1:]} or a "
            f"named-activation spelling ('save_names:<a,b>' / "
            f"'offload_names:<a,b>'), got {name!r}")
    kernel_residuals = policies.save_only_these_names(*KERNEL_RESIDUALS)
    if name == "dots_saveable":
        return policies.save_from_both_policies(policies.dots_saveable,
                                                kernel_residuals)
    return kernel_residuals


MODEL_INPUT_SPECS = {
    # name -> (example input shape without batch, num_classes or vocab)
    "enhanced_cnn": ((32, 32, 3), 10),
    "mlp": ((28, 28, 1), 10),
    "lenet5": ((28, 28, 1), 10),
    "resnet18": ((32, 32, 3), 10),
    "resnet50": ((224, 224, 3), 1000),
    "bert_base": ((128,), 30522),
    "bert_tiny": ((128,), 30522),
    "gpt2_small": ((128,), 50257),
    "gpt_small": ((128,), 50257),
    "gpt_tiny": ((128,), 50257),
    "llama_medium": ((1024,), 32000),
    "llama_tiny": ((128,), 32000),
    "vit_s16": ((224, 224, 3), 1000),
    "vit_b16": ((224, 224, 3), 1000),
    "vit_tiny": ((32, 32, 3), 10),
    **{name: ((128,), arch.vocab) for name, arch in ARCHS.items()},
}
