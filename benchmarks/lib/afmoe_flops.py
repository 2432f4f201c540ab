"""Operations and bytes that an AFMoE decoder (gated grouped-query
attention with window and full layers mixed, leading dense layers, sparse
layers of routed experts beside a shared one) REQUIRES, from shapes alone
(``lib/flops.py`` has the rules: 2 operations a multiply-add, forward once
and backward twice, no recomputation, no elementwise work, no lookup: the
gate's PRODUCT counts, its sigmoid and multiply and the six norms a layer
do not).

The architecture is the dict ``references/afmoe.py::arch_of`` gives.  What
is counted is what THIS chip is required to do: its held experts' share of
the routed products, its rows of the head; attention, the shared expert
and the dense layers whole, as the chip holds them.
"""

from __future__ import annotations

from benchmarks.lib.moe_flops import visible_keys_mean, window_flash_cost


def attention_matmul_params(arch: dict) -> float:
    """The five projections of gated grouped-query attention: q, k, v, the
    gate (as wide as q) and the output."""
    h, hd = arch["hidden"], arch["head_dim"]
    return 3 * h * arch["heads"] * hd + 2 * h * arch["kv_heads"] * hd


def layer_matmul_params(arch: dict, sparse: bool) -> float:
    """Weights of one layer that a token meets in a matrix product here.
    A sparse layer: the router, the shared expert and the expected share
    of its ``top_k`` experts that this chip holds; a dense one its MLP."""
    h = arch["hidden"]
    if not sparse:
        return attention_matmul_params(arch) + 3 * h * arch["dense_ffn"]
    held_share = arch["top_k"] * arch["held"][1] / arch["experts"]
    return (attention_matmul_params(arch) + h * arch["experts"]
            + 3 * h * arch["shared_ffn"] + held_share * 3 * h * arch["ffn"])


def attention_flops_per_token(arch: dict, seq_len: int) -> float:
    """QK^T and PV forward, twice that backward, each 2 * heads * head_dim
    operations a visible key, summed over the layers by their kind: the
    band for a sliding layer, the half square for a full one."""
    per_key = 3 * 2 * arch["heads"] * 2 * arch["head_dim"]
    return sum(per_key * visible_keys_mean(
        seq_len, arch["window"] if kind == "sliding" else None)
        for kind in arch["layer_types"])


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    dense = arch["layers"] - arch["moe_layers"]
    weights = (dense * layer_matmul_params(arch, False)
               + arch["moe_layers"] * layer_matmul_params(arch, True)
               + arch["hidden"] * arch["vocab"])
    return 6 * weights + attention_flops_per_token(arch, seq_len)


def flash_cost(batch: int, seq_len: int, arch: dict) -> dict:
    """Every layer's attention, forward + backward, as the flash kernels
    have to do it: ``moe_flops.window_flash_cost`` (the mask's own count of
    visible keys, grouped K and V) summed over the layers by their kind."""
    cost = {"flops": 0.0, "bytes": 0.0}
    for kind in arch["layer_types"]:
        one = window_flash_cost(batch, seq_len, arch, kind)
        for key in cost:
            cost[key] += one[key]
    return cost
