"""Operations and bytes that a latent-attention (MLA), routed-expert
decoder with leading dense layers REQUIRES, from shapes alone
(``lib/flops.py`` has the rules: 2 operations a multiply-add, forward once
and backward twice, no recomputation, no elementwise work, no lookup).

The architecture is the dict ``references/mla_moe.py::arch_of`` gives.
What is counted is what THIS chip is required to do: its held experts'
share of the routed products, its rows of the head; attention, the shared
experts and the dense layers whole, as the chip holds them.
"""

from __future__ import annotations

from benchmarks.lib.moe_flops import visible_keys_mean


def attention_matmul_params(arch: dict) -> float:
    """The four projections of latent attention in the expanded form: q,
    the joint down-projection to latent and rotary key, the up-projection
    to every head's key part and value, and the output."""
    h, heads = arch["hidden"], arch["heads"]
    return (h * heads * arch["qk_dim"] + h * (arch["latent"] + arch["qk_rope"])
            + arch["latent"] * heads * (arch["qk_nope"] + arch["v_dim"])
            + heads * arch["v_dim"] * h)


def layer_matmul_params(arch: dict, sparse: bool) -> float:
    """Weights of one layer that a token meets in a matrix product here.
    A sparse layer: the router, the shared experts and the expected share
    of its ``top_k`` experts that this chip holds; a dense one its MLP."""
    h = arch["hidden"]
    if not sparse:
        return attention_matmul_params(arch) + 3 * h * arch["dense_ffn"]
    held_share = arch["top_k"] * arch["held"][1] / arch["experts"]
    return (attention_matmul_params(arch) + h * arch["experts"]
            + 3 * h * arch["shared_ffn"] + held_share * 3 * h * arch["ffn"])


def attention_flops_per_token(arch: dict, seq_len: int) -> float:
    """QK^T (``qk_dim`` wide) and PV (``v_dim`` wide) forward, twice that
    backward, over the keys a causal query sees, in every layer."""
    per_key = 3 * 2 * arch["heads"] * (arch["qk_dim"] + arch["v_dim"])
    return arch["layers"] * per_key * visible_keys_mean(seq_len, None)


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    dense = arch["layers"] - arch["moe_layers"]
    weights = (dense * layer_matmul_params(arch, False)
               + arch["moe_layers"] * layer_matmul_params(arch, True)
               + arch["hidden"] * arch["vocab"])
    return 6 * weights + attention_flops_per_token(arch, seq_len)


def mla_flash_cost(batch: int, seq_len: int, arch: dict,
                   dtype_bytes: int = 2) -> dict:
    """One layer's causal attention, forward + backward, as the three
    flash kernels together have to do it at two widths: 2 * qk_dim + 2 *
    v_dim operations a visible key and head forward, twice that backward.
    Bytes: q, k (as the kernel is given it: a key a head, the rotary part
    copied), v and o once forward; q, k, v, o, do in and dq, dk, dv out
    backward: six tensors of the scores' width and six of the values'."""
    rows = batch * seq_len * arch["heads"]
    flops = (3 * 2 * rows * (arch["qk_dim"] + arch["v_dim"])
             * visible_keys_mean(seq_len, None))
    return {"flops": float(flops),
            "bytes": float(6 * rows * dtype_bytes
                           * (arch["qk_dim"] + arch["v_dim"]))}
