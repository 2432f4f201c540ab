"""Independent profiler cross-check of the roofline chain (VERDICT r3
'next' #5).

The bench's MFU/roofline story rests on XLA cost-model bytes divided by a
self-measured streaming bandwidth.  This tool captures a ``jax.profiler``
device trace of real train steps (ResNet-50 and ViT-S by default), parses
the perfetto JSON the profiler writes, and reports the op-level device
time breakdown — convolution/matmul (MXU) vs everything else — so the
"ResNet-50 is HBM-bound, transformers are MXU-bound" claim is checked by
an instrument that shares nothing with the harness that produced it.

Usage (TPU host):  python tools/profile_roofline.py [model ...]
Models: resnet50 vit_s16 bert_base gpt2_4k_flash llama llama_gqa4
(default: resnet50 vit_s16).  Writes the trace under
/tmp/jax_trace_<model> and prints a per-category device-time table, a
top-ops-by-name table (attributes Pallas custom calls, which the cost
model scores as zero-FLOP), and the fraction of wall covered by device
ops.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CONFIGS = {
    "resnet50": dict(name="resnet50", shape=(224, 224, 3), batch=128,
                     num_classes=1000, token=False),
    "vit_s16": dict(name="vit_s16", shape=(224, 224, 3), batch=128,
                    num_classes=1000, token=False),
    "bert_base": dict(name="bert_base", shape=(128,), batch=64,
                      num_classes=30522, token=True),
    # the bench's gpt2_4k_flash row (VERDICT r4 'next' #1: the one ladder
    # entry at ~half its own roofline, and the one workload the profiler
    # couldn't see — its time lives inside Pallas custom calls where
    # XLA's cost model reports neither flops nor bytes)
    "gpt2_4k_flash": dict(name="gpt2_small", shape=(4096,), batch=2,
                          num_classes=50257, token=True,
                          model_kw=dict(attention_impl="flash",
                                        max_len=4096)),
    # the modern-decoder ladder rows (RMSNorm/RoPE/SwiGLU + flash; GQA
    # variant shares the config via num_kv_heads)
    "llama": dict(name="llama_medium", shape=(1024,), batch=8,
                  num_classes=32000, token=True,
                  model_kw=dict(attention_impl="flash")),
    "llama_gqa4": dict(name="llama_medium", shape=(1024,), batch=8,
                       num_classes=32000, token=True,
                       model_kw=dict(attention_impl="flash",
                                     num_kv_heads=4)),
}


def build_step(cfg):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import softmax_cross_entropy

    model = get_model(cfg["name"], num_classes=cfg["num_classes"],
                      dtype=jnp.bfloat16, **cfg.get("model_kw", {}))
    rng = np.random.default_rng(0)
    if cfg["token"]:
        x = jnp.asarray(rng.integers(2, cfg["num_classes"],
                                     (cfg["batch"], *cfg["shape"])), jnp.int32)
        y = jnp.asarray(rng.integers(0, cfg["num_classes"],
                                     (cfg["batch"], *cfg["shape"])), jnp.int32)
    else:
        x = jnp.asarray(rng.normal(size=(cfg["batch"], *cfg["shape"])),
                        jnp.float32)
        y = jnp.asarray(rng.integers(0, cfg["num_classes"], cfg["batch"]),
                        jnp.int32)
    variables = jax.jit(lambda k: model.init(k, x[:1], train=False))(
        jax.random.key(0))
    has_bn = "batch_stats" in variables
    tx = optax.adam(1e-3)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            v = {"params": p}
            if has_bn:
                v["batch_stats"] = batch_stats
                out, mut = model.apply(v, x, train=True,
                                       mutable=["batch_stats"])
                bs = mut["batch_stats"]
            else:
                out = model.apply(v, x, train=True)
                bs = batch_stats
            return softmax_cross_entropy(out, y).mean(), bs

        (_, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), bs, new_opt

    state = (variables["params"], variables.get("batch_stats", {}),
             jax.jit(tx.init)(variables["params"]))
    return step, state


def parse_trace(trace_dir: str) -> dict | None:
    """Aggregate the profiler's "XLA Ops" lane of the TPU device process
    (lanes observed in the r4/r5 traces: Steps / XLA Modules / XLA Ops;
    not yet re-checked against this libtpu's trace).
    Each op event carries its MEASURED ``device_duration_ps`` plus the
    compiler's ``hlo_category``, ``model_flops`` and ``bytes_accessed`` —
    so per category we can report achieved TF/s and implied GB/s from an
    instrument independent of bench.py's chain timing."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not files:
        return None
    with gzip.open(files[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pids = {e["pid"]: e["args"].get("name", "")
            for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and "args" in e}
    dev_pids = {p for p, n in pids.items()
                if "tpu" in n.lower() or "device" in n.lower()}
    op_tids = {(e["pid"], e["tid"])
               for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"
               and "args" in e and e["args"].get("name") == "XLA Ops"
               and e["pid"] in dev_pids}
    cats: dict[str, dict] = {}
    ops: dict[str, float] = {}
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if (e.get("pid"), e.get("tid")) not in op_tids:
            continue
        args = e.get("args", {})
        cat = args.get("hlo_category", "uncategorized")
        c = cats.setdefault(cat, {"us": 0.0, "flops": 0.0, "bytes": 0.0})
        c["us"] += e["dur"]
        c["flops"] += float(args.get("model_flops", 0) or 0)
        c["bytes"] += float(args.get("bytes_accessed", 0) or 0)
        # per-op-name rollup: custom calls (Pallas kernels) all land in
        # one category with zero cost-model flops/bytes — the NAME is the
        # only way to attribute which kernel eats the time
        ops[e.get("name", "?")] = ops.get(e.get("name", "?"), 0.0) + e["dur"]
    total = sum(c["us"] for c in cats.values())
    if not total:
        return None
    return {"total_us": total,
            "by_category": dict(sorted(
                cats.items(), key=lambda kv: -kv[1]["us"])),
            "top_ops": dict(sorted(ops.items(),
                                   key=lambda kv: -kv[1])[:14])}


def main() -> None:
    import time

    import jax

    models = sys.argv[1:] or ["resnet50", "vit_s16"]
    for key in models:
        cfg = CONFIGS[key]
        step, state = build_step(cfg)
        state = step(state)           # compile + warm
        state = step(state)
        jax.block_until_ready(state)
        trace_dir = f"/tmp/jax_trace_{key}"
        t0 = time.perf_counter()
        with jax.profiler.trace(trace_dir):
            for _ in range(4):
                state = step(state)
            jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        parsed = parse_trace(trace_dir)
        print(f"\n=== {key}: 4 steps, wall {wall * 1e3:.1f} ms ===")
        if parsed is None:
            raise RuntimeError(
                f"{key}: the profiler wrote no parseable device trace "
                f"under {trace_dir}")
        tot = parsed["total_us"]
        print(f"  device op time total: {tot / 1e3:.1f} ms "
              f"({tot / 1e3 / wall / 10:.1f}% of wall)")
        print(f"  {'hlo_category':26s} {'time':>9s} {'share':>6s} "
              f"{'TF/s':>7s} {'GB/s':>7s}")
        for cat, c in parsed["by_category"].items():
            if c["us"] / tot < 0.005:
                continue
            sec = c["us"] / 1e6
            print(f"  {cat:26s} {c['us'] / 1e3:7.2f}ms "
                  f"{100 * c['us'] / tot:5.1f}% "
                  f"{c['flops'] / sec / 1e12:7.1f} "
                  f"{c['bytes'] / sec / 1e9:7.1f}")
        print("  top ops by device time:")
        for name, us in parsed["top_ops"].items():
            if us / tot < 0.01:
                continue
            print(f"    {name[:58]:58s} {us / 1e3:7.2f}ms "
                  f"{100 * us / tot:5.1f}%")


if __name__ == "__main__":
    main()
