"""The plain reference against the program's model at a tiny size on the
CPU: float32 agrees tightly, the program in bfloat16 stays inside limits
that the float8 control (the reference in the nearest lower precision, in
the program's place) breaks."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tinybench import OPTIMIZER, TINY_MIXES, TINY_SIZES

from benchmark import correct, generator
from benchmark.reference import pre_ln_transformer as ref

OPT = {k: v for k, v in OPTIMIZER.items() if k != "name"}
STEPS = 3


def _case(kind, seed):
    sizes = dict(TINY_SIZES, causal=kind == "lm")
    mix = TINY_MIXES[kind + "_tiny"]
    stream = generator.batches(mix, sizes["vocab_size"], 1, seed)
    return sizes, mix, [next(stream) for _ in range(STEPS)]


def _program(kind, sizes, mix, batches, params0, dtype):
    """The program's model through a plain optax loop: what the harness
    reads from the trainer, without the trainer."""
    from byteps_tpu.models import bert, gpt2
    kw = dict(hidden=sizes["hidden"], layers=sizes["layers"],
              heads=sizes["heads"], vocab_size=sizes["vocab_size"],
              max_seq=sizes["max_seq"], dtype=dtype)
    if kind == "mlm":
        cfg = bert.bert_config(**kw)
        loss_fn = lambda p, b: bert.mlm_loss(
            p, cfg, b, max_predictions=mix["max_predictions_per_seq"])
    else:
        cfg = gpt2.gpt2_config(**kw)
        loss_fn = lambda p, b: gpt2.causal_lm_loss(p, cfg, b)
    tx = optax.adamw(**OPT)
    grad = jax.jit(jax.value_and_grad(loss_fn))
    p, state, out = params0, tx.init(params0), {"loss": []}
    for i, batch in enumerate(batches):
        loss, g = grad(p, batch)
        if i == 0:
            out["grad_norm"] = np.asarray(ref.leaf_norms(g))
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
        out["loss"].append(float(loss))
    out["change_norm"] = np.asarray(ref.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, p, params0)))
    return out


def _reference(kind, sizes, mix, batches, params0, precision="float32"):
    return ref.train_steps(params0, batches, sizes, OPT, kind,
                           mix["reference_rows_per_block"], precision)


@pytest.mark.parametrize("kind", ["mlm", "lm"])
def test_float32_program_agrees_with_the_reference_tightly(kind):
    sizes, mix, batches = _case(kind, seed=2**31 + 11)
    params0 = ref.make_params(2**31 + 11, sizes)
    got = correct.readings(
        _program(kind, sizes, mix, batches, params0, "float32"),
        _reference(kind, sizes, mix, batches, params0))
    assert got["loss_rel"][0] < 2e-6
    assert got["grad_norm_rel"][0] < 2e-5
    assert got["change_norm_rel"][0] < 2e-4


# The limit that tells the precisions apart, at THIS size (hidden 64, two
# layers): over seeds 1 to 4 the bfloat16 program's change-norm RMS gap
# read 8.2e-4 to 2.3e-3 and the float8 control's 5.2e-3 to 1.3e-2 (CPU,
# PR 24). The cells' own limits are set the same way from chip readings
# at their own sizes (PERF.md).
TINY_CHANGE_NORM_RMS_LIMIT = 3.5e-3


@pytest.mark.parametrize("kind", ["mlm", "lm"])
def test_bfloat16_program_passes_where_the_float8_control_fails(kind):
    """The control, kept as a test at a size a test run can hold: the
    reference in the nearest precision below the stated one, put in the
    program's place, breaks the limit that the program in bfloat16 (what
    the configurations state) keeps, on three seeds, with room on both
    sides."""
    limits = {"change_norm_rms_rel": TINY_CHANGE_NORM_RMS_LIMIT}
    for seed in (1, 2, 3):
        sizes, mix, batches = _case(kind, seed)
        params0 = ref.make_params(seed, sizes)
        want = _reference(kind, sizes, mix, batches, params0)
        program = _program(kind, sizes, mix, batches, params0, "bfloat16")
        control = _reference(kind, sizes, mix, batches, params0, "float8")
        (sound,) = correct.compare(program, want, limits)
        (broken,) = correct.compare(control, want, limits)
        assert sound["ok"] and sound["value"] < limits[sound["check"]] / 1.4
        assert not broken["ok"]
        assert broken["value"] > limits[broken["check"]] * 1.4


def test_make_params_layout_is_the_programs():
    from byteps_tpu.models import bert, transformer
    sizes = dict(TINY_SIZES, causal=False)
    ours = ref.make_params(7, sizes)
    theirs = transformer.init_params(
        jax.random.PRNGKey(7),
        bert.bert_config(hidden=64, layers=2, heads=4, vocab_size=512,
                         max_seq=64))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
    same = ref.make_params(7, sizes)
    other = ref.make_params(2**31 + 7, sizes)
    assert (ours["blocks"]["qkv"] == same["blocks"]["qkv"]).all()
    assert not (ours["blocks"]["qkv"] == other["blocks"]["qkv"]).all()
    assert float(jnp.std(ours["embed"]["tok"])) == pytest.approx(0.02,
                                                                 rel=0.05)


def test_leaf_names_line_up_with_leaf_norms():
    sizes = dict(TINY_SIZES, causal=False)
    p = ref.make_params(1, sizes)
    names, norms = ref.leaf_names(p), ref.leaf_norms(p)
    assert len(names) == norms.shape[0] == 10 * sizes["layers"] + 4
    i = names.index("blocks.qkv[1]")
    assert float(norms[i]) == pytest.approx(
        float(jnp.linalg.norm(p["blocks"]["qkv"][1])), rel=1e-5)
    assert "embed.tok" in names and "final_ln.scale" in names


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    for node in ast.walk(tree):
        names = ([a.name for a in node.names]
                 if isinstance(node, ast.Import) else
                 [node.module or ""] if isinstance(node, ast.ImportFrom)
                 else [])
        assert not any("byteps" in n for n in names)
