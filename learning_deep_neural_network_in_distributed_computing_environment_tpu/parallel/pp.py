"""Pipeline parallelism (GPipe-style) over the ``pipe`` mesh axis.

Beyond-reference capability (the reference is data-parallel only,
SURVEY.md 2.3).  TPU-first formulation: the schedule is ONE SPMD program
under ``shard_map`` —

- the encoder's layer stack is stored stacked ([num_layers, ...] leaves,
  ``scan_layers=True`` models) and the leading layer axis is sharded over
  ``pipe``: stage ``s`` physically holds layers ``[s*L/P, (s+1)*L/P)`` and
  applies them with a layer ``scan``;
- the batch is split into M microbatches; at schedule step ``t`` stage
  ``s`` processes microbatch ``t - s`` (the classic GPipe diagonal), and
  activations move stage->stage with a single ring ``ppermute`` per step;
- invalid (bubble) steps compute on zero activations and their results
  are discarded by masking, keeping every device on the same program —
  the SPMD answer to the bubble, no host control flow;
- the backward pass is jax autodiff through the schedule scan: ppermute
  transposes to the reverse rotation, so cotangents flow backward through
  the pipeline automatically (GPipe's all-activations-live memory
  profile); the 1F1B schedule below (``onef1b_schedule``/``onef1b_loss``)
  interleaves backwards manually instead, capping in-flight residuals at
  O(stages) rather than O(microbatches).

Embeddings and the task head run replicated on every pipe stage (their
parameters are replicated; encoder activations dominate memory), which
keeps the loss and its gradients identical across the ``pipe`` axis —
shard_map's varying-axes autodiff then yields exact replicated-parameter
gradients with no post-hoc correction, as with tensor parallelism
(``parallel/tp.py``).

``gpipe_step``/``gpipe_finalize`` are the schedule bodies; they are shared
by the pure ``gpipe_schedule`` (unit tests) and the flax ``nn.scan``
driver inside ``models.bert`` (which must lift the scan so the stage
module's parameters broadcast across schedule steps).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def gpipe_step(apply_fn: Callable, xs: jnp.ndarray, axis_name: str,
               num_micro: int, carry, t):
    """One schedule step.  ``apply_fn(inp)`` runs this stage's layer block;
    ``xs`` [M, mb, ...] holds the microbatched pipeline inputs; ``carry``
    is ``(act_in, outs)``: the activation that just arrived from the
    predecessor stage and the finished-microbatch collection buffer."""
    p = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    act_in, outs = carry
    # stage 0 injects microbatch t; later stages consume what arrived
    x_t = xs[jnp.clip(t, 0, num_micro - 1)]
    inp = jnp.where(s == 0, x_t, act_in)
    y = apply_fn(inp)
    # the last stage finished microbatch t - (p-1) at this step
    done = t - (p - 1)
    record = (s == p - 1) & (done >= 0)
    outs = jnp.where(record, outs.at[jnp.clip(done, 0, num_micro - 1)].set(y),
                     outs)
    act_next = lax.ppermute(y, axis_name, [(i, (i + 1) % p) for i in range(p)])
    return act_next, outs


def gpipe_finalize(outs: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Broadcast the last stage's collected outputs to every stage so the
    replicated head computes one identical loss along ``pipe``."""
    p = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    return lax.psum(jnp.where(s == p - 1, outs, jnp.zeros_like(outs)),
                    axis_name)


def gpipe_schedule(stage_fn: Callable, xs: jnp.ndarray, axis_name: str,
                   num_micro: int) -> jnp.ndarray:
    """Pure-function pipeline: ``xs`` [M, mb, ...] -> [M, mb, ...] final
    activations, identical on every stage.  (Models go through the flax
    ``nn.scan`` path in ``models.bert`` instead — parameters must be
    lifted; this entry point serves parameterless stage fns and tests.)"""
    p = lax.axis_size(axis_name)

    def step(carry, t):
        return gpipe_step(stage_fn, xs, axis_name, num_micro, carry, t), None

    carry0 = gpipe_carry0(xs, axis_name)
    (_, outs), _ = lax.scan(step, carry0, jnp.arange(num_micro + p - 1))
    return gpipe_finalize(outs, axis_name)


def gpipe_carry0(xs: jnp.ndarray, axis_name: str):
    """Zero-initialized (act, outs) schedule carry, marked mesh-varying on
    ``axis_name`` — the loop body makes the carry varying (per-stage
    activations), so an invariant init would fail shard_map's scan carry
    type check."""
    vary = lambda a: lax.pcast(a, (axis_name,), to="varying")
    return vary(jnp.zeros_like(xs[0])), vary(jnp.zeros_like(xs))


def gpipe_apply_scanned(scanned, x: jnp.ndarray, axis_name: str,
                        pp_size: int, num_microbatches: int = 0
                        ) -> jnp.ndarray:
    """Run a flax ``nn.scan``-stacked block module through the GPipe
    schedule: microbatch the [B, ...] activations, lift the schedule scan
    so the stage parameters broadcast across steps, and return [B, ...]
    outputs identical on every stage.  Shared by ``models.bert`` and
    ``models.gpt``."""
    import flax.linen as nn

    m = num_microbatches or pp_size
    b = x.shape[0]
    if b % m:
        raise ValueError(f"per-worker batch {b} not divisible by "
                         f"{m} microbatches")
    xs = x.reshape(m, b // m, *x.shape[1:])

    def sched_step(mod, carry, t):
        # MoE aux-loss scale for this schedule step: bubble steps (stage s
        # has no microbatch at step t) contribute exactly zero, valid
        # steps 1/m so the m per-microbatch losses average to full-batch
        # scale.  The engine psums the summed aux over the pipe axis to
        # restore the loss's pipe-invariance (train.py).
        s = lax.axis_index(axis_name)
        valid = ((t - s >= 0) & (t - s < m))
        aux_scale = valid.astype(jnp.float32) / m
        return gpipe_step(lambda inp: mod(inp, aux_scale)[0], xs,
                          axis_name, m, carry, t), None

    sched = nn.scan(sched_step, variable_broadcast="params",
                    variable_axes={"aux": 0}, split_rngs={"params": False})
    steps = jnp.arange(m + pp_size - 1)
    (_, outs), _ = sched(scanned, gpipe_carry0(xs, axis_name), steps)
    return gpipe_finalize(outs, axis_name).reshape(x.shape)


# ----------------------------------------------------------------------
# 1F1B schedule (VERDICT r3 'next' #3)
# ----------------------------------------------------------------------
# GPipe above differentiates THROUGH the schedule scan, so every schedule
# step's stage activations are saved as autodiff residuals — an
# all-activations-live profile that scales with the microbatch count M
# (rematerialized per layer with --pp_remat, but still O(M) boundary
# activations).  1F1B interleaves one backward per forward in the steady
# state so stage s never holds more than P - s microbatches in flight.
#
# SPMD formulation: ONE lax.scan over T = 2M + 2P - 3 combined ticks; at
# tick t every stage does (at most) one fwd slot and one bwd slot, with
# closed-form index maps (derived from the Megatron-LM schedule): fwd µb
# i on stage s at tick i+s during warmup / 2i-P+1+2s in the steady
# state; bwd µb i at tick 2i+2(P-1)-s (the last stage backwards each µb
# in the same tick as its fwd; cotangents then travel one stage per
# tick).  Bubble slots compute on zeros and are masked — the same
# zero-compute-and-discard answer to the bubble as the GPipe path.
# Activations move forward one stage per warmup tick and one stage per
# TWO steady ticks, so each stage keeps a depth-2 incoming queue;
# cotangents move exactly one stage per tick.
#
# The trick that makes bwd-before-loss possible: the per-microbatch loss
# runs INSIDE the schedule on the last stage (head + CE per microbatch),
# seeding that microbatch's cotangent immediately.  A global masked-mean
# loss stays exact because its denominator is DATA-derived (mask and
# labels only) and is computed before the schedule starts.
#
# ``onef1b_loss``'s custom_vjp runs the whole fwd+bwd schedule in the
# FORWARD pass (the cotangent seed of a scalar loss is the literal 1.0),
# keeps the accumulated (stage, head, input) grads as residuals, and its
# backward is three scalar multiplies — so an outer ``jax.value_and_grad``
# (the engine's API) composes with the manual schedule for free, and
# embedding parameters OUTSIDE the schedule get exact gradients through
# the returned input cotangent.  Residual memory is therefore O(grads),
# independent of M; inside the schedule the live set is the [P] input
# ring buffer + the depth-2 queue (tests/test_pp.py compares profiles).


def _valid_fwd_index(t, s, p, m):
    """(µb index, valid) for the fwd slot of stage ``s`` at tick ``t``."""
    warm = t - s                       # i <= p-1-s: one stage per tick
    steady_num = t + p - 1 - 2 * s     # i = num/2 for i > p-1-s
    steady = steady_num // 2
    use_warm = warm <= p - 1 - s
    i = jnp.where(use_warm, warm, steady)
    ok = jnp.where(
        use_warm, warm >= 0,
        (steady_num % 2 == 0) & (steady > p - 1 - s))
    return jnp.clip(i, 0, m - 1), ok & (i >= 0) & (i < m)


def _valid_bwd_index(t, s, p, m):
    """(µb index, valid) for the bwd slot of stage ``s`` at tick ``t``.

    The last stage backwards µb i in the SAME tick as its fwd
    (Tf(i, p-1) = 2i + p - 1 for every i), and the cotangent travels one
    stage per tick, so Tb(i, s) = 2i + 2(p-1) - s uniformly — no warmup
    branch."""
    num = t - 2 * (p - 1) + s
    i = num // 2
    ok = (num % 2 == 0) & (i >= 0) & (i < m)
    return jnp.clip(i, 0, m - 1), ok


def onef1b_schedule(stage_fn: Callable, loss_fn: Callable, stage_params,
                    head_params, xs: jnp.ndarray, axis_name: str,
                    num_micro: int, masked_slots: bool = False,
                    stage_aux_weight: float | None = None):
    """Run the 1F1B pipeline schedule, computing loss AND gradients.

    ``stage_fn(stage_params, x)``: this stage's layer block (same
    structure on every stage; ``stage_params`` are the pipe-sharded local
    layers).  ``loss_fn(head_params, y, i)``: per-microbatch scalar loss
    partial (head + CE for microbatch ``i``; contributions must SUM to the
    global loss — divide by the data-derived global denominator inside);
    it may return ``(loss, aux)`` where ``aux`` is a pytree of per-
    microbatch metric sums (correct counts, token totals) accumulated
    across microbatches and NOT differentiated.
    ``xs`` [M, mb, ...]: microbatched schedule inputs (post-embedding).

    ``stage_aux_weight`` (1F1B x MoE, r5): when not None, ``stage_fn``
    returns ``(y, aux)`` where ``aux`` is this stage's scalar
    load-balance-loss sum for the microbatch (already at per-microbatch
    scale); the schedule adds ``weight * aux`` to the loss at every
    valid fwd slot and seeds the bwd slot's vjp with the matching
    ``weight`` cotangent on the aux output — so the auxiliary loss is
    differentiated through the stage exactly, preserving the custom-VJP
    linearity in the upstream scalar.

    Returns ``(loss, aux, gs, gh, gxs)``: scalar loss, summed aux, and
    the gradients w.r.t. stage_params / head_params / xs, all replicated
    along ``axis_name``.  Every tick recomputes the bwd slot's stage
    forward from the stored stage INPUT (per-layer remat by
    construction), so the in-flight residuals are O(stages) inputs."""
    p = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    m = num_micro
    has_aux = stage_aux_weight is not None
    # last bwd lands on stage 0 at tick 2(m-1) + 2(p-1)
    ticks = 2 * m + 2 * p - 3

    # Inside the engine the schedule runs under additional mesh axes (the
    # per-worker 'data' axis, at least), so every fresh zero / seed must
    # carry xs' full varying-axes set PLUS the pipe axis — otherwise the
    # scan carry types (and the vjp seed type) mismatch the body outputs.
    want_vma = set(jax.typeof(xs).vma) | {axis_name}

    def _vary_leaf(a):
        missing = tuple(sorted(want_vma - set(jax.typeof(a).vma)))
        return lax.pcast(a, missing, to="varying") if missing else a

    def vary(tree):
        return jax.tree_util.tree_map(_vary_leaf, tree)

    def loss_aux(hp, yy, i):
        out = loss_fn(hp, yy, i)
        return out if isinstance(out, tuple) else (out, {})

    aux_struct = jax.eval_shape(loss_aux, head_params, xs[0], 0)[1]

    # ring-buffer size: at stage s the fwd index runs ahead of the oldest
    # un-backwarded microbatch by up to 3(p-1-s)/2 in the steady state
    # (fwd advances every 2 ticks, the bwd of µb i lands 2(p-1-s) ticks
    # after its fwd), so floor(3(p-1)/2) + 1 slots are collision-free for
    # every stage — verified by exhaustive simulation of the index maps
    # for p in [2, 8], m up to 4p (min(p+1, m) clobbers from p = 5 up:
    # code-review r4 finding)
    nres = min(3 * (p - 1) // 2 + 1, m)
    zero_x = vary(jnp.zeros_like(xs[0]))
    carry0 = dict(
        q1=zero_x, q2=zero_x,              # incoming fwd activation queue
        gq=zero_x,                         # incoming cotangent (depth 1)
        res=vary(jnp.zeros((nres,) + xs.shape[1:], xs.dtype)),
        gs=vary(_zeros_tree(stage_params)),
        gh=vary(_zeros_tree(head_params)),
        gxs=vary(jnp.zeros_like(xs)),
        loss=vary(jnp.zeros((), jnp.float32)),
        aux=vary(_zeros_tree(aux_struct)),
    )

    def tick(carry, t):
        fi, f_ok = _valid_fwd_index(t, s, p, m)
        bi, b_ok = _valid_bwd_index(t, s, p, m)

        # Bubble slots are SKIPPED with lax.cond by default, not masked.
        # Legality: both predicates depend only on (pipe index s, tick
        # t), so every device sharing a stage takes the SAME branch —
        # Megatron psums over 'model' inside stage_fn / the vocab-
        # parallel head (1F1B x TP, r5) are entered by whole
        # model-groups or not at all, and the only cross-STAGE sync
        # points are the two ppermutes below, which stay lockstep.
        # Skipping roughly halves the schedule's compute vs compute-
        # then-mask (code-review r4: the head fwd+vjp alone otherwise
        # runs 2M+2P-3 times for M seeds).
        #
        # EXCEPTION (r5, found by unit bisect): a ``ppermute`` inside a
        # cond whose predicate varies over 'pipe' computes WRONG VALUES
        # (psum and all_gather in the same position are exact — the
        # rewrite of ppermute's paired sends under a varying-predicate
        # cond is what breaks).  ``masked_slots=True`` therefore runs
        # the FWD and BWD slots unconditionally and masks the results —
        # GPipe's proven semantics, exact by construction for ANY
        # collective — and the engine selects it whenever stage_fn
        # carries ring/Ulysses sequence-parallel attention.  The head
        # slot keeps its cond in either mode (no seq collective there).
        def mask_tree(ok, tree):
            return jax.tree_util.tree_map(
                lambda l: jnp.where(ok, l, jnp.zeros_like(l)), tree)

        # ---- fwd slot -------------------------------------------------
        # stage 0 injects xs[fi]; others consume the queue — depth 1 while
        # the producer was in ITS warmup (fi <= p-1-s), depth 2 in steady
        x_own = xs[fi]
        x_in = jnp.where(s == 0, x_own,
                         jnp.where(fi <= p - 1 - s, carry["q1"],
                                   carry["q2"]))

        def run_stage(x):
            out = stage_fn(stage_params, x)
            y, a = out if has_aux else (out, jnp.zeros((), jnp.float32))
            return vary(y), vary(a.astype(jnp.float32))

        if masked_slots:
            y, a_i = mask_tree(f_ok, run_stage(x_in))
        else:
            y, a_i = lax.cond(
                f_ok, run_stage,
                lambda x: (vary(jnp.zeros_like(x)),
                           vary(jnp.zeros((), jnp.float32))), x_in)
        res = jnp.where(f_ok, carry["res"].at[fi % nres].set(x_in),
                        carry["res"])

        # ---- last stage: per-microbatch head + loss + cotangent seed --
        is_last = s == p - 1
        seed_ok = is_last & f_ok

        def head_loss(hp, yy):
            return loss_aux(hp, yy, fi)

        def do_head(yy):
            # differentiate w.r.t. a VARYING view of the (replicated)
            # head params: varying-axes autodiff would auto-psum the
            # cotangent of an invariant primal over the pipe axis,
            # summing the other stages' garbage head grads in (and
            # paying a collective per tick); a varying primal keeps the
            # cotangent local, and the single psum at the end recovers
            # the replicated gradient from the zeros-elsewhere sum
            l_val, pull, aux_i = jax.vjp(head_loss, vary(head_params),
                                         yy, has_aux=True)
            dh_i, dy_i = pull(vary(jnp.ones((), l_val.dtype)))
            # vary() everything: branch avals must match no_head exactly,
            # and aux components that depend only on data (e.g. a token
            # count) would otherwise carry a smaller varying set
            return vary(l_val), vary(aux_i), vary(dh_i), vary(dy_i)

        def no_head(yy):
            return (vary(jnp.zeros((), jnp.float32)),
                    vary(_zeros_tree(aux_struct)),
                    vary(_zeros_tree(head_params)),
                    vary(jnp.zeros_like(yy)))

        # the head slot keeps the cond skip even under masked_slots: it
        # contains no sequence-parallel collective (chunk-local CE; the
        # vocab-parallel psum is over 'model', cond-proven under the
        # uniform model-group predicate), and masking it would run the
        # dominant [hidden, vocab] fwd+vjp 2M+2P-3 times per step
        # instead of M on the last stage (code-review r5)
        l_val, aux_i, dh_i, dy_i = lax.cond(seed_ok, do_head, no_head, y)
        loss = carry["loss"] + l_val
        if has_aux:
            # this stage's MoE load-balance contribution for the valid
            # fwd slot; summed across stages by the final pipe psum
            loss = loss + stage_aux_weight * a_i
        aux = jax.tree_util.tree_map(lambda a, v: a + v, carry["aux"],
                                     aux_i)
        gh = jax.tree_util.tree_map(lambda a, d: a + d, carry["gh"], dh_i)

        # ---- bwd slot -------------------------------------------------
        # cotangent source: the last stage seeds its own (fwd and bwd hit
        # the same microbatch in the same tick there); others use the
        # queue filled by the successor's ppermute last tick
        g_in = jnp.where(is_last, dy_i.astype(carry["gq"].dtype),
                         carry["gq"])
        # read the UPDATED buffer: the last stage's bwd hits the microbatch
        # whose input was stored by THIS tick's fwd slot
        x_res = res[bi % nres]

        def do_bwd(args):
            g, x = args
            # recompute this stage's forward from the stored input
            # (remat) and pull the cotangent back through it; with MoE
            # the aux output's cotangent IS the aux weight (the loss is
            # linear in it), so the load-balance gradient flows through
            # the same vjp
            if has_aux:
                (_, a_p), pull = jax.vjp(stage_fn, stage_params, x)
                # a_p * 0 + w: a weight-valued cotangent inheriting the
                # aux primal's dtype AND varying-axes set exactly
                ds, dx = pull((g.astype(x.dtype),
                               a_p * 0 + stage_aux_weight))
            else:
                ds, dx = jax.vjp(stage_fn, stage_params, x)[1](
                    g.astype(x.dtype))
            return vary(ds), vary(dx)

        def no_bwd(args):
            return (vary(_zeros_tree(stage_params)),
                    vary(jnp.zeros_like(x_res)))

        if masked_slots:
            ds_i, dx_i = (mask_tree(b_ok, t)
                          for t in do_bwd((g_in, x_res)))
        else:
            ds_i, dx_i = lax.cond(b_ok, do_bwd, no_bwd, (g_in, x_res))
        gs = jax.tree_util.tree_map(lambda a, d: a + d, carry["gs"], ds_i)
        gxs = jnp.where(b_ok & (s == 0),
                        carry["gxs"].at[bi].add(dx_i), carry["gxs"])

        # ---- ring moves (masked garbage rides the wire; consumers mask)
        fwd_ring = [(i, (i + 1) % p) for i in range(p)]
        bwd_ring = [(i, (i - 1) % p) for i in range(p)]
        q1 = lax.ppermute(jnp.where(f_ok, y, jnp.zeros_like(y)), axis_name,
                          fwd_ring)
        gq = lax.ppermute(jnp.where(b_ok, dx_i, jnp.zeros_like(dx_i)),
                          axis_name, bwd_ring)
        return dict(q1=q1, q2=carry["q1"], gq=gq, res=res, gs=gs, gh=gh,
                    gxs=gxs, loss=loss, aux=aux), None

    carry, _ = lax.scan(tick, carry0, jnp.arange(ticks))
    # loss / aux / head grads live on the last stage, input grads on
    # stage 0: psum replicates them (other stages contributed zeros)
    loss = lax.psum(carry["loss"], axis_name)
    aux = lax.psum(carry["aux"], axis_name)
    gh = lax.psum(carry["gh"], axis_name)
    gxs = lax.psum(carry["gxs"], axis_name)
    return loss, aux, carry["gs"], gh, gxs


def _zeros_tree(tree):
    """Zeros matching each leaf's shape, dtype AND varying-axes set.

    Under 1F1B x TP the stage/head gradient leaves are mesh-varying over
    'model' as well as 'pipe'/'data'; a plain ``jnp.zeros`` is invariant
    and would make the lax.cond branch avals (and scan carry types)
    mismatch the real-gradient branch.  Preserving the SOURCE leaf's vma
    here (the schedule's ``vary()`` then adds the pipe/data set on top)
    keeps both branches type-identical for any sharding."""
    def z(l):
        zz = jnp.zeros(l.shape, l.dtype)
        want = set(getattr(jax.typeof(l), "vma", None)
                   or getattr(l, "vma", None) or ())
        missing = tuple(sorted(
            want - set(getattr(jax.typeof(zz), "vma", ()) or ())))
        return lax.pcast(zz, missing, to="varying") if missing else zz
    return jax.tree_util.tree_map(z, tree)


def onef1b_loss(stage_fn: Callable, loss_fn: Callable, stage_params,
                head_params, xs: jnp.ndarray, *, axis_name: str,
                num_micro: int, masked_slots: bool = False,
                stage_aux_weight: float | None = None):
    """Differentiable entry point: ``(loss, aux) = onef1b_loss(...)``
    behaves like a plain function of (stage_params, head_params, xs)
    under ``jax.grad`` / ``value_and_grad`` (differentiate the loss;
    ``aux`` carries accumulated metric sums and is not differentiated),
    but its forward pass IS the fwd+bwd 1F1B schedule and its backward is
    three scalings of the stored gradients (exact: gradients are linear
    in the scalar upstream cotangent)."""

    @jax.custom_vjp
    def f(sp, hp, x):
        out = onef1b_schedule(stage_fn, loss_fn, sp, hp, x,
                              axis_name, num_micro,
                              masked_slots=masked_slots,
                              stage_aux_weight=stage_aux_weight)
        return out[0], out[1]

    def fwd(sp, hp, x):
        loss, aux, gs, gh, gxs = onef1b_schedule(
            stage_fn, loss_fn, sp, hp, x, axis_name, num_micro,
            masked_slots=masked_slots, stage_aux_weight=stage_aux_weight)
        return (loss, aux), (gs, gh, gxs)

    def bwd(resid, cot):
        gbar = cot[0]  # aux cotangent (cot[1]) is discarded: metrics only
        gs, gh, gxs = resid
        scale = lambda tree: jax.tree_util.tree_map(
            lambda l: (gbar * l.astype(gbar.dtype)).astype(l.dtype), tree)
        return scale(gs), scale(gh), scale(gxs)

    f.defvjp(fwd, bwd)
    loss, aux = f(stage_params, head_params, xs)
    # metrics-only contract made structural (advisor r4): without this a
    # caller differentiating an aux metric would get silent zeros from the
    # custom bwd's discarded cot[1]; stop_gradient declares it instead
    return loss, jax.tree_util.tree_map(lax.stop_gradient, aux)


def pp_param_specs(params, axis: str = "pipe"):
    """PartitionSpec tree for a ``scan_layers`` model: every leaf under the
    stacked ``layers`` collection is sharded over ``axis`` on its leading
    (layer) dimension, everything else replicated."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(p_, "key", str(p_)) for p_ in path]
        if "layers" in names:
            return P(axis, *([None] * (leaf.ndim - 1)))
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)
