"""The MLM head and its loss on the labelled rows only (ISSUE 29).

``BertForMLM`` carries ``labelled_rows_head``; on the plain path (one
device a worker) the engine gathers the labelled rows first into a buffer
of ``K`` rows (a quarter of the step's positions, rounded up to 128) and
runs head, cross-entropy and argmax there, and fills the buffer again
where more than ``K`` are labelled.  Held here: the gathered path
against the full one at every labelled count, the models without the
marker against the parent commit's jaxpr, who engages it, and the
parameter tree.  A comparison of two program shapes states its tolerance in
float32 ulps of the reference leaf's largest magnitude, beside the
difference read (PR 28's convention); two runs of one program stay
``assert_array_equal``."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import checkpoint as C
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
from learning_deep_neural_network_in_distributed_computing_environment_tpu.sim import SimEngine
from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import LocalSGDEngine

VOCAB = 512


def make_engine(devices, name, axes=None, **model_kw):
    """Engine and model on ``axes`` (default: one device a worker)."""
    cfg = Config(model=name, epochs_local=1, batch_size=8,
                 dataset="synthetic_mlm" if "bert" in name else "synthetic_lm",
                 compute_dtype="float32", augment=False,
                 aggregation_by="weights")
    model = get_model(name, scan_layers=True,
                      **{"num_classes": VOCAB, **model_kw})
    axes = axes or {"data": 1}
    mesh = build_mesh(axes, devices[:int(np.prod(list(axes.values())))])
    return LocalSGDEngine(model, mesh, cfg), model


def batch(shape, count, seed=0):
    """Token ids, labels and batch mask of one step.  Batch row 3 is
    padding (mask 0) and carries labels that must not count; ``count``
    positions of the real rows are labelled (``"all"``: every one;
    ``"every"``: no row is padding and every position is labelled)."""
    b, l = shape
    rng = np.random.default_rng(seed)
    x = rng.integers(0, VOCAB, shape).astype(np.int32)
    m = np.ones((b,), np.float32)
    m[3] = 0.0 if count != "every" else 1.0
    y = np.full(shape, -1, np.int32)
    y[3, ::2] = rng.integers(0, VOCAB, len(y[3, ::2]))
    real = np.flatnonzero(np.repeat(m, l) > 0)
    where = (real if count in ("all", "every")
             else rng.permutation(real)[:count])
    y.reshape(-1)[where] = rng.integers(0, VOCAB, len(where))
    return x, y, m, len(where)


@pytest.fixture(scope="module")
def bert(devices):
    engine, model = make_engine(devices, "bert_tiny", max_len=128)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((8, 64), jnp.int32))["params"]
    return engine, model, params


def step_of(engine, gathered):
    """``value_and_grad`` of the step's loss, the marker's path or the
    full one (the flag is read at trace time)."""
    def step(*a):
        engine.labelled_rows_head = gathered
        try:
            return jax.value_and_grad(engine._loss_and_metrics,
                                      has_aux=True)(*a)
        finally:
            engine.labelled_rows_head = True
    return jax.jit(step)


# (B, L) = (8, 64): N = 512, K = 128, up to 4 passes.  (5, 128): N = 640,
# K = 256, up to 3, the last buffer moved back over half of the second.
@pytest.mark.parametrize("shape,count,head_rows", [
    ((8, 64), 0, 128),          # the benchmark's short calls: no label
    ((8, 64), 1, 128),
    ((8, 64), 67, 128),         # typical: 15% of the 448 real positions
    ((8, 64), 128, 128),        # exactly K
    ((8, 64), 129, 256),        # K + 1: the buffer is filled twice
    ((8, 64), "all", 512),      # 448 real positions: four times
    ((5, 128), 257, 512),
    ((5, 128), "all", 512),     # 512 real positions of 640
    ((5, 128), "every", 768),   # the moved-back third buffer too
])
def test_gathered_head_is_the_full_head(bert, shape, count, head_rows):
    engine, _, params = bert
    x, y, m, labelled = batch(shape, count)
    (loss, (_, correct, total, counters)), grads = step_of(engine, True)(
        params, {}, x, y, m)
    (loss_f, (_, correct_f, total_f, counters_f)), grads_f = step_of(
        engine, False)(params, {}, x, y, m)
    assert float(total) == float(total_f) == labelled
    assert float(correct) == float(correct_f)
    assert float(counters["head_rows"]) == head_rows
    assert "head_rows" not in counters_f
    # allowed: 8 ulps of the loss, 64 ulps of a leaf's largest entry.  Read
    # over these cases on three seeds each: 4, and 17 (an encoder kernel:
    # the rows' cotangents reach it summed in another order)
    assert abs(float(loss) - float(loss_f)) <= 8 * np.spacing(
        np.float32(abs(float(loss_f))))
    for g, g_f in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(grads_f)):
        g, g_f = np.asarray(g), np.asarray(g_f)
        atol = 64 * float(np.spacing(np.abs(g_f).max()))
        np.testing.assert_allclose(g, g_f, rtol=0, atol=atol)
    if not labelled:
        assert float(loss) == 0.0
        assert not any(np.asarray(g).any()
                       for g in jax.tree_util.tree_leaves(grads))
    # one program, run twice: bit for bit
    (loss_2, _), grads_2 = step_of(engine, True)(params, {}, x, y, m)
    assert float(loss_2) == float(loss)
    for g, g_2 in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(grads_2)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_2))


@pytest.mark.parametrize("count", [67, 129])      # one pass, two
def test_eval_step_sums(bert, count):
    engine, _, params = bert
    x, y, m, labelled = batch((8, 64), count, seed=1)

    def sums(gathered):
        engine.labelled_rows_head = gathered
        try:
            eval_step = engine._make_step_fns(False)[1]
            return jax.jit(lambda p, *inp: eval_step((p, {}), inp)[1])(
                params, x, y, m)
        finally:
            engine.labelled_rows_head = True
    (num, correct, total), (num_f, correct_f, total_f) = sums(True), sums(False)
    assert float(total) == float(total_f) == labelled
    assert float(correct) == float(correct_f)
    assert abs(float(num) - float(num_f)) <= 8 * np.spacing(
        np.float32(float(num_f)))


def jaxpr_pins(engine, params, x):
    """Hashes of the jaxprs of the step's loss with its gradient and of
    the evaluation step, source locations and addresses left out."""
    y, m = x, jnp.ones((x.shape[0],), jnp.float32)
    eval_step = engine._make_step_fns(False)[1]
    texts = (
        jax.make_jaxpr(jax.value_and_grad(
            engine._loss_and_metrics, has_aux=True))(params, {}, x, y, m),
        jax.make_jaxpr(lambda p: eval_step((p, {}), (x, y, m)))(params))
    clean = lambda t: re.sub(r" at (/[^\s\]]*|0x[0-9a-f]+)", "", str(t))
    return tuple(hashlib.sha256(clean(t).encode()).hexdigest()[:16]
                 for t in texts)


# Read off the parent commit (15db948) by ``jaxpr_pins``: the models
# without the marker, and BertForMLM on the full path, lower
# ``_loss_and_metrics`` and ``eval_step`` to what they lowered to before
# the row buffer came.  A PR that changes these programs on purpose reads
# new pins (ISSUE 33 did for ``mellum2_tiny``: the routed layer's own row
# buffer changes every sparse model's program; and so did the way back into
# the tokens becoming one kernel, ``ops/moe_combine.py``).
@pytest.mark.parametrize("name,model_kw,gathered,pins", [
    ("gpt_tiny", {"max_len": 64}, None, ("c57bfdbd21618319", "a1ab63707a8bd3de")),
    ("mellum2_tiny", {"num_classes": 1000}, None, ("666f79f13a1d7820", "9785f970214b23a8")),
    ("bert_tiny", {"max_len": 64}, False, ("0c671134bb022f23", "449a1135f0dcdc7a")),
])
def test_unmarked_programs_are_the_parents(devices, name, model_kw, gathered,
                                           pins):
    engine, model = make_engine(devices, name, **model_kw)
    assert engine.labelled_rows_head is (gathered is not None)
    if gathered is not None:
        engine.labelled_rows_head = gathered
    x = jnp.zeros((8, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), x)["params"]
    assert jaxpr_pins(engine, params, x) == pins
    out = jax.eval_shape(engine._loss_and_metrics, params, {}, x, x,
                         jnp.ones((8,), jnp.float32))
    assert "head_rows" not in out[1][3]


@pytest.mark.parametrize("axes", [
    {"data": 2, "model": 2},        # vocab-parallel head
    {"data": 2, "pipe": 2},         # GPipe / 1F1B head slot
    {"data": 2, "seq": 2},          # a sequence-split batch
    {"data": 2, "fsdp": 2},         # a batch split over fsdp
])
def test_only_the_plain_path_engages(devices, axes):
    assert make_engine(devices, "bert_tiny",
                       axes={"data": 2})[0].labelled_rows_head
    assert not make_engine(devices, "bert_tiny",
                           axes=axes)[0].labelled_rows_head


def test_simulated_workers_take_the_full_path(devices):
    """Under ``vmap`` the loop would run for the fullest worker's count on
    every worker."""
    model = get_model("bert_tiny", num_classes=VOCAB, max_len=64,
                      scan_layers=True)
    cfg = Config(model="bert_tiny", dataset="synthetic_mlm", sim_workers=2,
                 aggregation_by="weights", compute_dtype="float32",
                 augment=False)
    engine = SimEngine(model, build_mesh({"data": 1}, devices[:1]), cfg)
    assert not engine.labelled_rows_head


def test_parameter_tree_and_checkpoint_round_trip(bert, devices, tmp_path,
                                                  assert_within_ulps):
    """``init`` runs the full forward, so 'encode' and 'head' add no
    parameter and name none anew; a checkpoint restores into the same
    tree; encode then head is the full forward."""
    engine, model, params = bert
    assert sorted(params) == ["layers", "ln_emb", "mlm_decoder", "mlm_dense",
                              "mlm_ln", "pos_emb", "tok_emb"]
    x = batch((8, 64), 0)[0]
    state = engine.init_state(jax.random.key(0), x)
    assert (jax.tree_util.tree_structure(state.params)
            == jax.tree_util.tree_structure(params))
    path = C.save_checkpoint(str(tmp_path), state, global_epoch=1)
    template = engine.init_state(jax.random.key(1), x)
    restored, _ = C.restore_checkpoint(path, template)
    assert (jax.tree_util.tree_structure(restored.params)
            == jax.tree_util.tree_structure(state.params))
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    split = jax.jit(lambda p: model.apply(
        {"params": p}, model.apply({"params": p}, x, mode="encode"),
        mode="head"))(params)
    assert_within_ulps(split, full, 4)        # read: 0


def test_engine_is_released_after_the_call():
    """The custom derivative's thunks live in the traced round program; if
    one closed over the engine, engine and compiled program would outlive
    the call (on the chip: 30 MB of HBM a call, PERF.md section 6)."""
    import gc
    import weakref
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import config_from_args
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
    cfg = config_from_args([
        "--model", "bert_tiny", "--dataset", "synthetic_mlm",
        "--batch_size", "8", "--epochs_local", "1", "--epochs_global", "1",
        "--limit_train_samples", "64", "--limit_eval_samples", "16",
        "--num_workers", "1", "--aggregation_by", "weights",
        "--compile_cache_dir", "", "--device", "cpu"])
    results = train_global(cfg, progress=False)
    assert results["round_timings"][0]["head_rows"] == 256.0   # 8 x 128 / 4
    engine = weakref.ref(results["engine"])
    program = weakref.ref(results["engine"]._programs["round"])
    results.clear()
    gc.collect()
    assert engine() is None and program() is None
