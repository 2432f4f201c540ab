"""graftlint rule fixtures: true positives AND true negatives per rule
(R1-R5), suppression-comment + baseline-file behavior, and the two
acceptance gates — the repo lints clean against its checked-in baseline,
and an injected true positive flips the exit to non-zero."""

import json
import os
import subprocess
import sys

import pytest

from tools.graftlint.core import (_suppressed, _suppressions,
                                  apply_baseline, lint_paths,
                                  load_baseline, write_baseline, Finding)
from tools.graftlint.rules import lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(
    REPO, "learning_deep_neural_network_in_distributed_computing"
          "_environment_tpu")
BASELINE = os.path.join(REPO, "tools", "graftlint", "baseline.json")


def rules_for(src: str) -> list[str]:
    """Rule ids firing on a snippet, suppression comments honored."""
    per_line, file_level = _suppressions(src)
    return [r.rule for r in lint_source(src, "snippet.py")
            if not _suppressed(r, per_line, file_level)]


# --------------------------------------------------------------------
# R1: host sync in traced regions
# --------------------------------------------------------------------
class TestR1HostSync:
    def test_item_in_jit_flagged(self):
        src = """
import jax
@jax.jit
def f(x):
    return x.item()
"""
        assert rules_for(src) == ["R1"]

    def test_item_on_host_fn_clean(self):
        src = """
def host(x):
    return x.item()
"""
        assert rules_for(src) == []

    def test_np_asarray_on_traced_flagged(self):
        src = """
import jax, numpy as np
def body(x):
    return np.asarray(x) + 1
g = jax.jit(body)
"""
        assert rules_for(src) == ["R1"]

    def test_float_of_traced_flagged_but_static_float_clean(self):
        src = """
import jax, jax.numpy as jnp
@jax.jit
def f(x, k=4):
    y = jnp.sum(x)
    bad = float(y)
    return bad
def outer(self, x):
    k = 3
    good = float(k)   # host int -> host float, no sync
    return good
"""
        assert rules_for(src) == ["R1"]

    def test_implicit_bool_branch_flagged(self):
        src = """
import jax
@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""
        assert rules_for(src) == ["R1"]

    def test_is_none_branch_clean(self):
        src = """
import jax
@jax.jit
def f(x, d=None):
    if d is not None:
        x = x + d
    return x
"""
        assert rules_for(src) == []

    def test_scan_body_is_traced(self):
        src = """
from jax import lax
def run(xs):
    def body(c, x):
        return c, x.tolist()
    return lax.scan(body, 0.0, xs)
"""
        assert rules_for(src) == ["R1"]


# --------------------------------------------------------------------
# R2: retrace hazards
# --------------------------------------------------------------------
class TestR2Retrace:
    def test_jit_in_loop_flagged(self):
        src = """
import jax
def run(xs):
    out = []
    for x in xs:
        out.append(jax.jit(lambda a: a + 1)(x))
    return out
"""
        assert "R2" in rules_for(src)

    def test_module_scope_jit_clean(self):
        src = """
import jax
f = jax.jit(lambda a: a + 1)
def run(x):
    return f(x)
"""
        assert rules_for(src) == []

    def test_construct_and_call_flagged(self):
        src = """
import jax
def run(g, x):
    return jax.jit(g)(x)
"""
        assert rules_for(src) == ["R2"]

    def test_local_jit_then_call_flagged(self):
        src = """
import jax
def run(g, x):
    fn = jax.jit(g)
    return fn(x)
"""
        assert rules_for(src) == ["R2"]

    def test_jit_decorated_local_def_then_call_flagged(self):
        src = """
import jax
def evaluate(x):
    @jax.jit
    def run(a):
        return a + 1
    return run(x)
"""
        assert "R2" in rules_for(src)

    def test_jit_decorated_module_def_clean(self):
        src = """
import jax
@jax.jit
def run(a):
    return a + 1
def evaluate(x):
    return run(x)
"""
        assert rules_for(src) == []

    def test_builder_returning_jit_clean(self):
        src = """
import jax
def build(fn):
    return jax.jit(fn, donate_argnums=(0,))
"""
        assert rules_for(src) == []

    def test_unhashable_static_arg_flagged(self):
        src = """
import jax
def f(a, b):
    return a
out = jax.jit(f, static_argnums=(1,))(1, [2, 3])
"""
        assert "R2" in rules_for(src)


# --------------------------------------------------------------------
# R3: collective axis-name vocabulary
# --------------------------------------------------------------------
class TestR3AxisNames:
    def test_unknown_axis_flagged(self):
        src = """
from jax import lax
def body(x):
    return lax.psum(x, "workers")
"""
        assert rules_for(src) == ["R3"]

    def test_vocabulary_axes_clean(self):
        src = """
from jax import lax
def body(x):
    y = lax.pmean(x, "data")
    return lax.psum(y, ("data", "model"))
"""
        assert rules_for(src) == []

    def test_axis_constant_name_clean(self):
        src = """
from jax import lax
from pkg.mesh import DATA_AXIS
def body(x):
    return lax.psum(x, DATA_AXIS)
"""
        assert rules_for(src) == []

    def test_tuple_with_typo_flagged(self):
        src = """
from jax import lax
def body(x):
    return lax.pmean(x, ("data", "modl"))
"""
        assert rules_for(src) == ["R3"]

    def test_axis_outside_enclosing_shard_map_specs_flagged(self):
        # mesh is a VARIABLE (as in all real call sites): the check keys
        # on the statically-visible specs alone
        src = """
import jax
from jax.sharding import PartitionSpec as P
from jax import lax

def inner(x):
    return lax.psum(x, "model")

prog = jax.shard_map(inner, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P("data"))
"""
        assert rules_for(src) == ["R3"]

    def test_dynamic_specs_skip_subset_check(self):
        src = """
import jax
from jax import lax

def inner(x):
    return lax.psum(x, "model")

prog = jax.shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                     out_specs=out)
"""
        assert rules_for(src) == []


# --------------------------------------------------------------------
# R4: donation hygiene
# --------------------------------------------------------------------
class TestR4Donation:
    def test_use_after_donate_flagged(self):
        src = """
import jax
def step(g, state, x):
    f = jax.jit(g, donate_argnums=(0,))
    out = f(state, x)
    return state  # graftlint reads the donated buffer again
"""
        assert "R4" in rules_for(src)

    def test_rebound_donated_name_clean(self):
        src = """
import jax
def step(g, state, x):
    f = jax.jit(g, donate_argnums=(0,))
    state = f(state, x)
    return state
"""
        assert "R4" not in rules_for(src)

    def test_rebinding_in_later_statement_clears_donated_name(self):
        src = """
import jax
def step(g, state, x):
    f = jax.jit(g, donate_argnums=(0,))
    out = f(state, x)
    state = out[0]
    return state  # reads the NEW binding, not the donated buffer
"""
        assert "R4" not in rules_for(src)

    def test_read_of_donated_name_before_rebind_still_flagged(self):
        src = """
import jax
def step(g, state, x):
    f = jax.jit(g, donate_argnums=(0,))
    out = f(state, x)
    norm = state.sum()   # donated buffer read BEFORE the rebind
    state = out[0]
    return state, norm
"""
        assert "R4" in rules_for(src)

    def test_jit_of_shard_map_without_donation_flagged(self):
        src = """
import jax
from jax import shard_map
fn = shard_map(lambda x: x, mesh=None, in_specs=None, out_specs=None)
prog = jax.jit(fn)
"""
        assert rules_for(src) == ["R4"]

    def test_jit_of_shard_map_with_donation_clean(self):
        src = """
import jax
from jax import shard_map
fn = shard_map(lambda x: x, mesh=None, in_specs=None, out_specs=None)
prog = jax.jit(fn, donate_argnums=(0,))
"""
        assert rules_for(src) == []

    def test_apply_stage_tracker_use_after_donate_flagged(self):
        # ISSUE 9 fixture: the shard-resident apply-stage program donates
        # BOTH the grads and the round-optimizer tracker rows
        # (train._build_sync donate=(0, 1)); reading the donated tracker
        # input after the call is the exact hazard class R4 exists for
        src = """
import jax
def sync_round(sync, grads, round_opt):
    prog = jax.jit(sync, donate_argnums=(0, 1))
    norm, new_opt = prog(grads, round_opt)
    stale = round_opt  # donated tracker rows read after the call
    return norm, stale
"""
        assert "R4" in rules_for(src)

    def test_apply_stage_tracker_rebound_clean(self):
        # the engine's real shape: the donated tracker name is rebound to
        # the program's output before any further read
        src = """
import jax
def sync_round(sync, grads, round_opt):
    prog = jax.jit(sync, donate_argnums=(0, 1))
    norm, round_opt = prog(grads, round_opt)
    return norm, round_opt
"""
        assert "R4" not in rules_for(src)

    def test_enter_gather_resident_use_after_donate_flagged(self):
        # ISSUE 11 fixture: the round-entry gather program DONATES the
        # resident bucket shards into the gather (train.py streamed
        # "enter" cache / comms.make_resident_gather donate=True);
        # reading the donated resident input after the call would touch
        # freed 1/N shard buffers — the exact hazard class R4 exists for
        src = """
import jax
def enter_round(gather, resident):
    prog = jax.jit(gather, donate_argnums=(0,))
    params = prog(resident)
    shard_bytes = resident  # donated resident shards read after the call
    return params, shard_bytes
"""
        assert "R4" in rules_for(src)

    def test_enter_gather_resident_rebound_clean(self):
        # the engine's real shape: the resident name is rebound to the
        # NEXT sync's scatter output before any further read — the
        # steady-state resident cycle (gather consumes, scatter renews)
        src = """
import jax
def enter_round(gather, sync, resident):
    prog = jax.jit(gather, donate_argnums=(0,))
    params = prog(resident)
    resident = sync(params)
    return resident
"""
        assert "R4" not in rules_for(src)

    def test_buddy_hop_state_use_after_donate_flagged(self):
        # ISSUE 12 fixture: the buddy-redundant sync program donates the
        # state whose shard rows it re-scatters AND ring-copies
        # (train._build_sync donate=(0,...)); reading the donated
        # state's OLD buddy rows after the call — instead of the fresh
        # copy the hop just produced — touches freed buffers, the exact
        # hazard class R4 exists for (the driver therefore drops the
        # previous buddy before dispatch and reads only the output's)
        src = """
import jax
def sync_round(sync, params, residual):
    prog = jax.jit(sync, donate_argnums=(0, 1))
    out = prog(params, residual)
    stale = residual  # donated EF rows read after the buddy-hop sync
    return out, stale
"""
        assert "R4" in rules_for(src)

    def test_buddy_hop_rebound_to_fresh_copy_clean(self):
        # the engine's real shape: every protected row (resident shards,
        # residual, buddy) is rebound to the sync program's OUTPUT dict
        # before any further read — the fresh ring copy replaces the
        # donated generation
        src = """
import jax
def sync_round(sync, params, residual):
    prog = jax.jit(sync, donate_argnums=(0, 1))
    out = prog(params, residual)
    params = out["out"]
    residual = out["residual"]
    buddy = out["buddy"]
    return params, residual, buddy
"""
        assert "R4" not in rules_for(src)

    def test_hier_outer_residual_use_after_donate_flagged(self):
        # ISSUE 13 fixture: the hierarchical standalone sync donates the
        # params AND both EF residual levels (train._build_sync
        # donate=(0, 1, 2)); reading the donated OUTER residual rows
        # after the call — instead of the fresh generation the program's
        # output dict carries — touches freed 1/W-span buffers, the
        # exact hazard class R4 exists for
        src = """
import jax
def hier_round(sync, params, residual, outer_residual):
    prog = jax.jit(sync, donate_argnums=(0, 1, 2))
    out = prog(params, residual, outer_residual)
    stale = outer_residual  # donated DCN EF rows read after the sync
    return out, stale
"""
        assert "R4" in rules_for(src)

    def test_hier_outer_residual_rebound_clean(self):
        # the engine's real shape: every donated level is rebound to the
        # program's output dict before any further read (round_start /
        # round_streamed_start)
        src = """
import jax
def hier_round(sync, params, residual, outer_residual):
    prog = jax.jit(sync, donate_argnums=(0, 1, 2))
    out = prog(params, residual, outer_residual)
    params = out["out"]
    residual = out["residual"]
    outer_residual = out["outer_residual"]
    return params, residual, outer_residual
"""
        assert "R4" not in rules_for(src)

    def test_sim_stacked_state_use_after_donate_flagged(self):
        # ISSUE 14 fixture: the simulated round program donates the
        # whole worker-STACKED TrainState (sim.SimEngine._build_round,
        # donate_argnums=(0,)) — with hundreds of simulated workers the
        # stacked carry is the chip's dominant allocation, so a read of
        # the donated input after dispatch touches freed [N, ...]
        # buffers (and a declined donation would silently DOUBLE the
        # state memory the whole lab exists to save)
        src = """
import jax
def sim_loop(sim_round, state, x, y, m):
    prog = jax.jit(sim_round, donate_argnums=(0,))
    new_state, metrics = prog(state, x, y, m)
    probe = state  # donated stacked carry read after dispatch
    return new_state, metrics, probe
"""
        assert "R4" in rules_for(src)

    def test_sim_stacked_state_rebound_to_output_clean(self):
        # the engine's real shape: the caller rebinds its state name to
        # the round's output before any further read (driver round loop)
        src = """
import jax
def sim_loop(sim_round, state, x, y, m):
    prog = jax.jit(sim_round, donate_argnums=(0,))
    state, metrics = prog(state, x, y, m)
    return state, metrics
"""
        assert "R4" not in rules_for(src)

    def test_stale_presync_state_use_after_overlap_flagged(self):
        # ISSUE 16 fixture: under --sync_staleness the stale sync
        # program reads a round's trained state WITHOUT donating it
        # while the NEXT round's program donates those same buffers —
        # device-safe (the runtime orders the donating write after the
        # already-dispatched sync's read) but host-unsafe: after the
        # overlapped dispatch the donated pre-sync state must never be
        # read again on the host, exactly the in-flight contract R4
        # polices
        src = """
import jax
def overlapped_rounds(round_prog, stale_sync, state, batch):
    prog = jax.jit(round_prog, donate_argnums=(0,))
    pending = stale_sync(state)     # in flight: reads, never donates
    new_state = prog(state, batch)  # donates the same buffers
    probe = state   # donated pre-sync state read after the dispatch
    return new_state, pending, probe
"""
        assert "R4" in rules_for(src)

    def test_stale_delivery_rebinds_to_blend_clean(self):
        # the engine's real shape (train._deliver_oldest / the round
        # loop): at the fence every consumer rebinds its state name to
        # the delivery fold's output — the delivered blend replaces the
        # donated generation before any further read
        src = """
import jax
def overlapped_rounds(round_prog, stale_sync, deliver, state, batch):
    prog = jax.jit(round_prog, donate_argnums=(0,))
    pending = stale_sync(state)
    state = prog(state, batch)
    state = deliver(state, pending)   # the delivered blend
    return state
"""
        assert "R4" not in rules_for(src)

    def test_chunked_prefill_pool_use_after_donate_flagged(self):
        # ISSUE 17 fixture: the [1, C] chunk program donates BOTH page
        # pools every call (engine._build_prefill_program
        # donate_argnums=(1, 2)) and the scheduler calls it once per
        # chunk — reading the pre-chunk kc/vc between chunks touches the
        # freed generation of the dominant serve allocation, the exact
        # hazard class R4 exists for
        src = """
import jax
def prefill_loop(chunk_step, params, kc, vc, chunk, tail):
    prog = jax.jit(chunk_step, donate_argnums=(1, 2))
    tok, logits, kc2, vc2 = prog(params, kc, vc, chunk)
    warm = kc  # donated page pool read between chunks
    tok, logits, kc2, vc2 = prog(params, kc2, vc2, tail)
    return tok, warm
"""
        assert "R4" in rules_for(src)

    def test_chunked_prefill_pool_rebound_each_chunk_clean(self):
        # the engine's real shape: every chunk rebinds the pool names to
        # the returned pools in the same statement, so the next chunk
        # (and the interleaved decode step) only ever sees the current
        # generation
        src = """
import jax
def prefill_loop(chunk_step, params, kc, vc, chunks):
    prog = jax.jit(chunk_step, donate_argnums=(1, 2))
    for c in chunks:
        tok, logits, kc, vc = prog(params, kc, vc, c)
    return tok, kc, vc
"""
        assert "R4" not in rules_for(src)

    def test_draft_cache_read_after_verify_dispatch_flagged(self):
        # ISSUE 18 fixture: the speculative tick runs the draft's
        # donated decode step k times, then dispatches the target's
        # fused verify.  The draft pools' CARRY names still point at the
        # generation the last draft step donated — reading one after the
        # verify dispatch (e.g. to "snapshot" draft KV for rollback)
        # touches freed pages.  Rollback is arithmetic on the accepted
        # length, never a pool read — exactly the contract R4 polices
        src = """
import jax
def spec_tick(draft_step, verify, params, dkc, dvc, kc, vc, burst, y):
    dprog = jax.jit(draft_step, donate_argnums=(1, 2))
    for j in range(4):
        y, dkc2, dvc2 = dprog(params, dkc, dvc, y)
    vprog = jax.jit(verify, donate_argnums=(1, 2))
    emitted, acc, kc, vc = vprog(params, kc, vc, burst)
    snapshot = dkc  # donated draft carry read after verify dispatch
    return emitted, acc, snapshot
"""
        assert "R4" in rules_for(src)

    def test_draft_cache_rebound_to_output_clean(self):
        # the engine's real shape (scheduler._spec_step via
        # ServeEngine.decode / .verify): every draft step rebinds the
        # draft pool names to its outputs in the same statement, and
        # accept/rollback is computed from `acc` alone — no pool read
        # ever sees a stale generation
        src = """
import jax
def spec_tick(draft_step, verify, params, dkc, dvc, kc, vc, burst, y):
    dprog = jax.jit(draft_step, donate_argnums=(1, 2))
    for j in range(4):
        y, dkc, dvc = dprog(params, dkc, dvc, y)
    vprog = jax.jit(verify, donate_argnums=(1, 2))
    emitted, acc, kc, vc = vprog(params, kc, vc, burst)
    return emitted, acc, dkc, dvc, kc, vc
"""
        assert "R4" not in rules_for(src)

    def test_rebound_name_no_longer_shard_map_clean(self):
        src = """
import jax
from jax import shard_map
fn = shard_map(lambda x: x, mesh=None, in_specs=None, out_specs=None)
prog = jax.jit(fn, donate_argnums=(0,))
fn = make_plain_step()
other = jax.jit(fn)
"""
        assert rules_for(src) == []

    def test_jit_before_shard_map_assignment_not_matched(self):
        src = """
import jax
from jax import shard_map
fn = make_plain_step()
prog = jax.jit(fn)
fn = shard_map(lambda x: x, mesh=None, in_specs=None, out_specs=None)
"""
        assert rules_for(src) == []


# --------------------------------------------------------------------
# R6: checkpoint_name remat-label vocabulary (ISSUE 15)
# --------------------------------------------------------------------
class TestR6RematNames:
    def test_typo_label_flagged(self):
        # the hazard: a typo'd label never matches a --remat_policy
        # save_names:/offload_names: set — silent save-nothing
        src = """
from pkg.compat import checkpoint_name
def block(x):
    return checkpoint_name(x, "atn_out")
"""
        assert rules_for(src) == ["R6"]

    def test_vocabulary_labels_clean(self):
        src = """
from jax.ad_checkpoint import checkpoint_name
def block(x):
    a = checkpoint_name(x, "attn_out")
    f = checkpoint_name(a, name="mlp_out")
    return checkpoint_name(a + f, "block_out")
"""
        assert rules_for(src) == []

    def test_dotted_spelling_and_kwarg_typo_flagged(self):
        src = """
import jax
def block(x):
    return jax.ad_checkpoint.checkpoint_name(x, name="block_output")
"""
        assert rules_for(src) == ["R6"]

    def test_dynamic_label_skipped(self):
        # same silence rule as R3's dynamic axis args: a computed label
        # is someone else's contract
        src = """
from pkg.compat import checkpoint_name
def block(x, label):
    return checkpoint_name(x, label)
"""
        assert rules_for(src) == []

    def test_remat_vocab_discovered_from_models_init(self):
        # the vocabulary is DISCOVERED from models/__init__.py's
        # REMAT_NAMES constant, like R3's mesh.py axis discovery
        from tools.graftlint.core import discover_remat_vocab
        vocab = discover_remat_vocab([PKG])
        # a tuple over two lines since ISSUE 35's kernel residuals
        assert set(vocab) == {"attn_out", "mlp_out", "block_out",
                              "moe_dispatch", "flash_out", "flash_lse"}

    def test_custom_vocab_overrides_default(self):
        src = """
from pkg.compat import checkpoint_name
def block(x):
    return checkpoint_name(x, "my_custom_site")
"""
        assert [r.rule for r in lint_source(src, "s.py")] == ["R6"]
        assert [r.rule for r in lint_source(
            src, "s.py",
            remat_vocab=frozenset({"my_custom_site"}))] == []


# --------------------------------------------------------------------
# R5: dtype-promotion traps
# --------------------------------------------------------------------
class TestR5DtypeTraps:
    def test_np_float64_in_traced_flagged(self):
        src = """
import jax, numpy as np
@jax.jit
def f(x):
    return x * np.float64(0.5)
"""
        assert rules_for(src) == ["R5"]

    def test_astype_builtin_float_flagged(self):
        src = """
import jax
@jax.jit
def f(x):
    return x.astype(float)
"""
        assert rules_for(src) == ["R5"]

    def test_zeros_like_scan_carry_flagged(self):
        src = """
import jax, jax.numpy as jnp
from jax import lax
@jax.jit
def f(xs):
    def body(c, x):
        return c + x, None
    out, _ = lax.scan(body, jnp.zeros_like(xs[0]), xs)
    return out
"""
        assert rules_for(src) == ["R5"]

    def test_zeros_like_with_pinned_dtype_clean(self):
        src = """
import jax, jax.numpy as jnp
from jax import lax
@jax.jit
def f(xs):
    def body(c, x):
        return c + x, None
    out, _ = lax.scan(
        body, jnp.zeros_like(xs[0], dtype=jnp.float32), xs)
    return out
"""
        assert rules_for(src) == []

    def test_zeros_like_with_positional_dtype_clean(self):
        src = """
import jax, jax.numpy as jnp
from jax import lax
@jax.jit
def f(xs):
    def body(c, x):
        return c + x, None
    out, _ = lax.scan(body, jnp.zeros_like(xs[0], jnp.float32), xs)
    return out
"""
        assert rules_for(src) == []


# --------------------------------------------------------------------
# Suppression comments
# --------------------------------------------------------------------
class TestSuppression:
    BAD = """
import jax
@jax.jit
def f(x):
    return x.item(){comment}
"""

    def test_same_line_disable(self):
        src = self.BAD.format(
            comment="  # graftlint: disable=R1 -- fixture")
        assert rules_for(src) == []

    def test_line_above_disable(self):
        src = """
import jax
@jax.jit
def f(x):
    # graftlint: disable=R1 -- fixture
    return x.item()
"""
        assert rules_for(src) == []

    def test_wrong_rule_does_not_suppress(self):
        src = self.BAD.format(comment="  # graftlint: disable=R3")
        assert rules_for(src) == ["R1"]

    def test_disable_all(self):
        src = self.BAD.format(comment="  # graftlint: disable=all")
        assert rules_for(src) == []

    def test_file_level_disable(self):
        src = "# graftlint: disable-file=R1\n" + self.BAD.format(comment="")
        assert rules_for(src) == []

    def test_comment_inside_string_is_not_a_suppression(self):
        src = """
import jax
@jax.jit
def f(x):
    s = "# graftlint: disable=R1"
    return x.item()
"""
        assert rules_for(src) == ["R1"]


# --------------------------------------------------------------------
# Baseline behavior
# --------------------------------------------------------------------
class TestBaseline:
    def _findings(self, tmp_path, src):
        p = tmp_path / "mod.py"
        p.write_text(src)
        return lint_paths([str(p)], repo_root=str(tmp_path))

    BAD = """
import jax
@jax.jit
def f(x):
    return x.item()
"""

    def test_baselined_finding_is_consumed(self, tmp_path):
        findings = self._findings(tmp_path, self.BAD)
        assert [f.rule for f in findings] == ["R1"]
        bl_path = tmp_path / "baseline.json"
        write_baseline(findings, str(bl_path))
        new, accepted = apply_baseline(
            self._findings(tmp_path, self.BAD), load_baseline(str(bl_path)))
        assert new == [] and len(accepted) == 1
        assert accepted[0].baselined

    def test_extra_finding_on_top_of_baseline_reported(self, tmp_path):
        findings = self._findings(tmp_path, self.BAD)
        bl_path = tmp_path / "baseline.json"
        write_baseline(findings, str(bl_path))
        worse = self.BAD + """
@jax.jit
def g(x):
    return x.tolist()
"""
        new, accepted = apply_baseline(
            self._findings(tmp_path, worse), load_baseline(str(bl_path)))
        assert len(accepted) == 1
        assert [f.rule for f in new] == ["R1"]
        assert "tolist" in new[0].line_text

    def test_line_drift_does_not_invalidate_baseline(self, tmp_path):
        findings = self._findings(tmp_path, self.BAD)
        bl_path = tmp_path / "baseline.json"
        write_baseline(findings, str(bl_path))
        shifted = "\n\n\n# moved down\n" + self.BAD
        new, accepted = apply_baseline(
            self._findings(tmp_path, shifted), load_baseline(str(bl_path)))
        assert new == [] and len(accepted) == 1

    def test_overlapping_paths_lint_each_file_once(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(self.BAD)
        findings = lint_paths([str(tmp_path), str(p)],
                              repo_root=str(tmp_path))
        assert len(findings) == 1  # dir + file-in-dir is ONE lint

    def test_unparseable_file_reports_not_crashes(self, tmp_path):
        p = tmp_path / "broken.py"
        p.write_text("def f():\n        x = 1\n      y = 2\n")
        findings = lint_paths([str(p)], repo_root=str(tmp_path))
        assert [f.rule for f in findings] == ["R2"]
        assert "does not parse" in findings[0].message

    def test_scoped_write_baseline_keeps_other_files_entries(
            self, tmp_path):
        a, b = tmp_path / "a.py", tmp_path / "b.py"
        a.write_text(self.BAD)
        b.write_text(self.BAD)
        bl_path = tmp_path / "baseline.json"
        write_baseline(lint_paths([str(tmp_path)],
                                  repo_root=str(tmp_path)), str(bl_path))
        # re-write from a NARROWER scope: b.py's entry must survive
        old = load_baseline(str(bl_path))
        write_baseline(lint_paths([str(a)], repo_root=str(tmp_path)),
                       str(bl_path), old, scoped_files={"a.py"})
        kept = load_baseline(str(bl_path))
        assert ("b.py", "R1", "return x.item()") in kept.entries

    def test_justifications_carry_over_on_rewrite(self, tmp_path):
        findings = self._findings(tmp_path, self.BAD)
        bl_path = tmp_path / "baseline.json"
        write_baseline(findings, str(bl_path))
        data = json.loads(bl_path.read_text())
        data["entries"][0]["justification"] = "known metric readback"
        bl_path.write_text(json.dumps(data))
        write_baseline(self._findings(tmp_path, self.BAD), str(bl_path),
                       load_baseline(str(bl_path)))
        data2 = json.loads(bl_path.read_text())
        assert data2["entries"][0]["justification"] == \
            "known metric readback"


# --------------------------------------------------------------------
# Acceptance gates
# --------------------------------------------------------------------
class TestRepoGate:
    def test_package_lints_clean_against_checked_in_baseline(self):
        findings = lint_paths([PKG], repo_root=REPO)
        new, _ = apply_baseline(findings, load_baseline(BASELINE))
        assert new == [], "\n".join(str(f) for f in new)

    def test_cli_exit_codes(self, tmp_path):
        env = dict(os.environ, PYTHONPATH=REPO)
        clean = subprocess.run(
            [sys.executable, "-m", "tools.graftlint"],
            cwd=REPO, env=env, capture_output=True, text=True)
        assert clean.returncode == 0, clean.stdout + clean.stderr
        bad = tmp_path / "injected.py"
        bad.write_text("""
import jax
@jax.jit
def f(x):
    return x.item()
""")
        injected = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", PKG, str(bad)],
            cwd=REPO, env=env, capture_output=True, text=True)
        assert injected.returncode == 1, injected.stdout + injected.stderr
        assert "R1" in injected.stdout

    def test_axis_vocab_discovered_from_mesh_py(self):
        # ISSUE 13: the hierarchical mesh's ``slice`` outer axis is an
        # X_AXIS constant in mesh.py, so R3's vocabulary discovery must
        # pick it up — collectives over "slice" lint clean, typos don't
        from tools.graftlint.core import discover_axis_vocab
        vocab, constants = discover_axis_vocab([PKG])
        assert {"data", "model", "pipe", "seq", "expert",
                "fsdp", "slice"} <= set(vocab)
        assert constants.get("DATA_AXIS") == "data"
        assert constants.get("SLICE_AXIS") == "slice"

    def test_vmapped_code_without_axis_names_lints_clean(self):
        # ISSUE 14: the simulator's whole point is that vmap'd per-worker
        # code carries NO mesh axis names — the cross-worker reductions
        # are stacked math (sequential fold, roll).  R3's collective-
        # axis-name vocabulary check must have nothing to say about it.
        src = """
import jax
import jax.numpy as jnp
from jax import lax
def sim_sync(local_round, stacked, x):
    outs = jax.vmap(local_round)(stacked, x)
    def add(acc, row):
        return acc + row, None
    folded, _ = lax.scan(add, outs[0], outs[1:])
    return (outs + jnp.roll(outs, 1, axis=0)) / 2.0, folded
"""
        assert "R3" not in rules_for(src)

    def test_slice_axis_collectives_lint_clean(self):
        # the hierarchical program's shape: psum_scatter over the inner
        # axis, ppermute over the discovered "slice" outer axis
        src = """
from jax import lax
def hier(m, ns):
    r1 = lax.ppermute(m, "slice", [(i, (i + 1) % ns)
                                   for i in range(ns)])
    return (m + r1) / 2.0
"""
        assert "R3" not in rules_for(src)
        bad = src.replace('"slice"', '"slices"')
        assert "R3" in rules_for(bad)

    def test_finding_str_and_key(self):
        f = Finding("a.py", 3, 1, "R1", "msg", "  x.item()  ")
        assert f.key == ("a.py", "R1", "x.item()")
        assert "a.py:3:1: R1 msg" == str(f)
