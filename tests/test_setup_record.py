"""The trainer's record of its own start (``common/setup_record.py``,
``training._recorded``): where it opens and closes, what the one
``jax.monitoring`` listener joins into it, what a trace's choices leave
in it, and that nothing of it runs in a step. CPU, tiny sizes; what the
record reads on the chip is the benchmark's business
(``benchmark/metrics/trainer.step_*``)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.common import setup_record
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.training import DistributedTrainer, ShardedTrainer

SPANS = ("bps.setup.init", "bps.setup.place_params", "bps.setup.opt_init",
         "bps.setup.build_step", "bps.setup.first_step",
         "bps.setup.step_memory")
PARENTS = {"bps.setup.init": None, "bps.setup.first_step": None,
           "bps.setup.step_memory": "bps.setup.first_step"}


def _loss(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _batch(rows=8):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(rows, 4).astype(np.float32),
            "y": rng.randn(rows, 2).astype(np.float32)}


def _trainer(kind="distributed"):
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    params = {"w": jnp.ones((4, 2), jnp.float32)}
    if kind == "sharded":
        from jax.sharding import PartitionSpec as P
        return ShardedTrainer(_loss, params, {"w": P()}, optax.sgd(0.1),
                              mesh, batch_spec=P("data"))
    return DistributedTrainer(_loss, params, optax.sgd(0.1), mesh=mesh)


def _step_entries(rec):
    return [e for e in rec["compiles"] if e["step"]]


@pytest.mark.parametrize("kind", ["distributed", "sharded"])
def test_the_record_opens_in_the_constructor_and_closes_after_one_step(kind):
    trainer = _trainer(kind)
    rec = trainer.setup_record()
    assert not rec["closed"] and setup_record._current is rec
    assert [s["name"] for s in rec["spans"]] == list(SPANS[:4])
    assert "step" in vars(trainer)              # the one-call wrapper
    trainer.step(_batch())
    assert rec["closed"] and setup_record._current is None
    assert "step" not in vars(trainer)          # from now on the class's own
    assert [s["name"] for s in rec["spans"]] == list(SPANS)
    trainer.step(_batch())
    assert len(rec["spans"]) == len(SPANS)


@pytest.mark.parametrize("name", SPANS)
def test_every_span_names_its_parent_and_lies_inside_it(name):
    trainer = _trainer()
    trainer.step(_batch())
    spans = {s["name"]: s for s in trainer.setup_record()["spans"]}
    s = spans[name]
    assert s["end"] >= s["start"]
    assert s["parent"] == PARENTS.get(name, "bps.setup.init")
    if s["parent"]:
        outer = spans[s["parent"]]
        assert outer["start"] <= s["start"] and s["end"] <= outer["end"]
    if name == "bps.setup.place_params":
        assert s["args"] == {"bytes": 4 * 2 * 4}
    if name == "bps.setup.first_step":
        assert s["args"] == {"step_num": 0}


@pytest.mark.parametrize("lower_first,lowerings", [
    (None, 1), ("same_batch", 1), ("host_batch", 2)],
    ids=["first_step_alone", "lowered_before_as_the_harness_does",
         "lowered_before_on_another_placement"])
def test_the_steps_compile_is_joined_from_jaxs_events(lower_first, lowerings):
    """One entry a lowering of the step function, whoever called ``lower``.
    The harness lowers ``trainer._step_fn`` itself before the first step, on
    the device batch the step is then given: JAX hands the call that
    lowering and its executable, and the step is lowered ONCE. A lowering
    for another placement of the batch is a second one."""
    trainer = _trainer()
    placed = trainer.shard_batch(_batch())
    if lower_first:
        trainer._step_fn.lower(
            trainer.params, trainer.opt_state,
            placed if lower_first == "same_batch" else _batch()).compile()
    trainer.step(placed)
    rec = trainer.setup_record()
    assert rec["step_funs"] == ("step",)
    entries = _step_entries(rec)
    assert sum(e["lower_s"] > 0 for e in entries) == lowerings
    for e in entries:
        assert e["fun_name"] == "step"
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["compile_s"] > 0
        assert "inside" not in e
    # between the constructor and the first step no span is open
    assert entries[0]["span"] == (None if lower_first
                                  else "bps.setup.first_step")
    if lowerings == 2:
        assert entries[1]["span"] == "bps.setup.first_step"
    # the constructor's small programs are the others, under its spans
    others = [e for e in rec["compiles"] if not e["step"]]
    assert others and all(
        e["span"].startswith("bps.setup.") for e in others)


def test_a_new_batch_shape_after_the_close_is_a_recompile_with_its_step():
    trainer = _trainer()
    for _ in range(3):
        trainer.step(_batch())
    rec = trainer.setup_record()
    compiles = len(rec["compiles"])
    assert rec["recompiles"] == []
    trainer.step(_batch(rows=4))                # a short last batch
    entry, = rec["recompiles"]
    assert entry["step_num"] == 3 and entry["step"]
    assert entry["trace_s"] > 0 and entry["compile_s"] > 0
    # an unrelated function compiled outside ``step`` belongs to nobody
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
    assert len(rec["recompiles"]) == 1 and len(rec["compiles"]) == compiles


def test_the_listener_is_registered_once_over_two_trainers(monkeypatch):
    from jax._src import monitoring
    first, second = _trainer(), _trainer()
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(setup_record._on_duration) == 1
    # the newer trainer's record is the open one until the older steps
    assert setup_record._current is second.setup_record()
    first.step(_batch())
    second.step(_batch())
    for trainer in (first, second):
        assert len(_step_entries(trainer.setup_record())) <= 1
        assert trainer.setup_record()["closed"]
    assert len(_step_entries(first.setup_record())) == 1


def test_a_compile_nested_in_a_trace_is_marked_inside_it():
    """An eager product on constants inside the step's trace compiles a
    program of its own while the trace's clock runs."""
    def loss(params, batch):
        with jax.ensure_compile_time_eval():    # compiled at trace time
            scale = float(jnp.tanh(jnp.ones(())) * 2.0)
        return scale * _loss(params, batch)

    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer = DistributedTrainer(loss, {"w": jnp.ones((4, 2))},
                                 optax.sgd(0.1), mesh=mesh)
    before = len(trainer.setup_record()["compiles"])
    trainer.step(_batch())
    during = trainer.setup_record()["compiles"][before:]
    nested = [e for e in during if not e["step"]]
    assert nested and all(e["inside"] == "step" for e in nested)
    assert "inside" not in _step_entries(trainer.setup_record())[0]


# ------------------------------------------- the step's memory account

LOWER_OR_COMPILE = [event for event, part in setup_record.PARTS.items()
                    if part in ("lower_s", "compile_s")]


@pytest.mark.parametrize("kind", ["distributed", "sharded"])
def test_the_first_step_leaves_the_compilers_account_and_lowers_nothing(kind):
    """``step_memory`` is read from the executable the step ran: between
    the step's return and the record's close JAX traces (its cached
    trace, 0 s) and neither lowers nor compiles."""
    import threading
    trainer = _trainer(kind)
    rec, step_fn, events = trainer.setup_record(), trainer._step_fn, []

    def listen(event, secs, **_):
        events.append(
            (event, setup_record._open_span(rec, threading.get_ident())))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        trainer.step(_batch())
    finally:
        jax._src.monitoring.unregister_event_duration_listener(listen)
    during = [e for e, open_span in events
              if open_span == "bps.setup.step_memory"]
    assert during == ["/jax/core/compile/jaxpr_trace_duration"]
    assert {e for e, _ in events} >= set(LOWER_OR_COMPILE)     # the step's
    assert len(_step_entries(rec)) == 1
    assert trainer._step_fn is step_fn and "step" not in vars(trainer)
    sizes = rec["step_memory"]["step"]
    assert set(rec["step_memory"]) == {"step"} and set(sizes) == {
        "args", "out", "alias", "temp", "code", "peak"}
    stats = step_fn.lower(trainer.params, trainer.opt_state,
                          trainer.shard_batch(_batch())).compile(
                              ).memory_analysis()
    assert sizes["peak"] == (sizes["args"] + sizes["out"] - sizes["alias"]
                             + sizes["temp"] + sizes["code"]) > 0
    assert (sizes["args"], sizes["temp"]) == (
        stats.argument_size_in_bytes, stats.temp_size_in_bytes)


def test_step_account_walks_the_jaxpr_once_and_only_when_asked(monkeypatch):
    from byteps_tpu.common import kept_values
    walks = []
    real = kept_values.kept
    monkeypatch.setattr(kept_values, "kept",
                        lambda jaxpr: (walks.append(jaxpr), real(jaxpr))[1])
    trainer = _trainer()
    rec = trainer.setup_record()
    assert trainer.step_account() == {"step_memory": {}, "kept": None}
    trainer.step(_batch())
    trainer.step(_batch())
    assert not walks and rec["kept"] is None and rec["trainer"]() is trainer
    first = trainer.step_account()
    assert len(walks) == 1 and trainer._step_traced is None
    assert first == trainer.step_account() and len(walks) == 1
    assert first == {"step_memory": rec["step_memory"], "kept": rec["kept"]}
    # the tiny step keeps what the squared error's backward reads
    assert first["kept"]["bytes"] > 0
    assert first["kept"]["bytes"] == sum(first["kept"]["by_scope"].values()) \
        == sum(size for _, size in first["kept"]["by_name"].values())


def test_a_ps_branch_trainer_reads_as_nothing(monkeypatch):
    """The PS branches dispatch no one program: no ``_step_fn``, no span,
    no account."""
    import byteps_tpu as bps
    monkeypatch.setenv("BPS_ENABLE_PS", "1")
    bps.init(config=bps.Config.from_env())
    try:
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        trainer = DistributedTrainer(_loss, {"w": jnp.ones((4, 2))},
                                     optax.sgd(0.1), mesh=mesh,
                                     name="account-ps")
        trainer.step(_batch())
        rec = trainer.setup_record()
        assert not hasattr(trainer, "_step_fn") and rec["closed"]
        assert "bps.setup.step_memory" not in [
            s["name"] for s in rec["spans"]]
        assert trainer.step_account() == {"step_memory": {}, "kept": None}
        trainer.close()
    finally:
        bps.shutdown()


def _decoder_case(tiny: str):
    from byteps_tpu.models import decoder
    cfg = getattr(decoder, tiny)(remat=True)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 64)).astype(np.int32)
    return (lambda p, b: decoder.causal_lm_loss(p, cfg, b)), params, tokens, 1


def _scanned_bert_case():
    """Three BERT layers under ``lax.scan`` at shapes the flash kernels
    take (traced for a TPU, never lowered): their names are kept."""
    from byteps_tpu.models import bert, transformer
    cfg = bert.bert_config(hidden=128, layers=3, heads=2, vocab_size=512,
                           max_seq=128, remat=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 512, (2, 128)).astype(np.int32)
    targets = np.where(rng.rand(2, 128) < 0.15, tokens, -1).astype(np.int32)
    return ((lambda p, b: bert.mlm_loss(p, cfg, b)), params,
            (tokens, targets), cfg.layers)


KEPT_CASES = {"afmoe_tiny": lambda: _decoder_case("afmoe_tiny"),
              "qwen3_next_tiny": lambda: _decoder_case("qwen3_next_tiny"),
              "bert_scanned": _scanned_bert_case}
KEPT_NAMES = {"afmoe_tiny": {"moe_plan", "moe_weights", "post_norm_in"},
              "qwen3_next_tiny": {"moe_plan", "moe_weights"},
              "bert_scanned": {"flash_out", "flash_lse"}}


def _residuals(loss, params, batch):
    """(values, bytes) of ``saved_residuals`` less the function's own
    arguments and constants."""
    from jax._src.ad_checkpoint import saved_residuals
    sizes = [int(np.prod(aval.shape)) * aval.dtype.itemsize
             for aval, why in saved_residuals(loss, params, batch)
             if not why.startswith("from ")]
    return len(sizes), sum(sizes)


@pytest.mark.parametrize("case", sorted(KEPT_CASES))
def test_kept_equals_jaxs_saved_residuals_by_total_and_by_name(
        monkeypatch, case):
    """The reference names nothing a kept float (JAX wraps it in a
    ``reduce_precision``), so it is asked once a name, with the models'
    policy keeping that name alone: what the name adds to the policy that
    keeps none is the name's values and bytes. A scan's stacked value is
    one of the reference's and a value a layer of the walk's."""
    from byteps_tpu.common.kept_values import kept
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    loss, params, batch, trips = KEPT_CASES[case]()
    got = kept(jax.jit(jax.value_and_grad(loss)).trace(params, batch).jaxpr)
    assert got["bytes"] == _residuals(loss, params, batch)[1]
    names = set(got["by_name"]) - {"layer_input", "unnamed"}
    assert names == KEPT_NAMES[case]
    only = jax.checkpoint_policies.save_only_these_names
    reference = {}
    for keep in [None, *sorted(names)]:
        with monkeypatch.context() as patch:
            patch.setattr(jax.checkpoint_policies, "save_only_these_names",
                          lambda *all_names, keep=keep: only(
                              *(n for n in all_names if n == keep)))
            reference[keep] = _residuals(loss, params, batch)
    for name in names:
        values, size = (a - b for a, b in zip(reference[name],
                                              reference[None]))
        assert got["by_name"][name] == [trips * values, size] and size > 0
    # what no name keeps: every layer's input and the custom derivatives'
    rest = [got["by_name"][n] for n in ("layer_input", "unnamed")]
    assert sum(size for _, size in rest) == reference[None][1]
    layers = trips if trips > 1 else len(params["layers"])
    assert rest[0][0] == layers
    assert sum(got["by_scope"].values()) == got["bytes"]
    assert all(scope == "" or scope.startswith("bps.")
               for scope in got["by_scope"])


@pytest.mark.parametrize("cast", [True, False], ids=["a_named_cast",
                                                     "the_argument"])
def test_an_operand_a_jitted_derivative_hands_on_is_counted_once(cast):
    """``grouped_matmul``'s shape: a jitted ``custom_vjp`` whose forward
    returns its weight among the residuals. The jit's result IS its
    operand, one buffer: the named cast counts once, under its name, and
    the step's own argument not at all."""
    from jax.ad_checkpoint import checkpoint_name
    from byteps_tpu.common.kept_values import kept

    @jax.custom_vjp
    def product(x, w):
        return x @ w

    product.defvjp(lambda x, w: (x @ w, (x, w)),
                   lambda res, g: (g @ res[1].T, res[0].T @ g))

    def layer(x, w):
        if cast:
            w = checkpoint_name(w.astype(jnp.bfloat16), "held")
        return jnp.tanh(jax.jit(product)(x.astype(w.dtype), w))

    policy = jax.checkpoint_policies.save_only_these_names("held")

    def loss(w, x):
        return jax.checkpoint(layer, policy=policy)(x * 2.0, w).sum()

    w, x = jnp.ones((8, 4), jnp.float32), jnp.ones((2, 8), jnp.float32)
    got = kept(jax.jit(jax.value_and_grad(loss)).trace(w, x).jaxpr)
    assert got["by_name"] == dict(
        {"held": [1, 8 * 4 * 2]} if cast else {},
        layer_input=[1, 2 * 8 * 4])


def _call_attention(shape):
    from byteps_tpu.ops.flash_attention import attention
    q = jnp.zeros(shape, jnp.float32)
    jax.eval_shape(lambda q: attention(q, q, q), q)


def _call_flash(seq, **kwargs):
    """A head-major call of the kernels themselves (width 128)."""
    from byteps_tpu.ops.flash_attention import flash_attention
    q = jnp.zeros((1, seq, 2, 128), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(q, q, q, True, **kwargs), q)


def _call_ssd(packed):
    from byteps_tpu.ops import ssd as S
    bsz, s, heads, p, groups, n = (1, 256, 4, 64, 2, 128) if packed != "odd" \
        else (1, 64, 4, 8, 2, 16)
    chunk = 128 if packed != "odd" else 16
    f32 = jnp.float32
    dt, a, d = (jnp.ones((bsz, s, heads), f32), -jnp.ones((heads,), f32),
                jnp.ones((heads,), f32))
    if packed == "packed":
        xbc = jnp.zeros((bsz, s, heads * p + 2 * groups * n), jnp.bfloat16)
        return jax.eval_shape(
            lambda xbc: S.ssd_packed(xbc, dt, a, d, groups, n, chunk), xbc)
    x = jnp.zeros((bsz, s, heads, p), jnp.bfloat16)
    b = jnp.zeros((bsz, s, groups, n), jnp.bfloat16)
    jax.eval_shape(lambda x, b: S.ssd(x, dt, a, b, b, d, chunk), x, b)


def _call_grouped_matmul(width):
    from byteps_tpu.ops.grouped_matmul import grouped_matmul
    lhs = jnp.zeros((256, width), jnp.bfloat16)
    w = jnp.zeros((2, width, width), jnp.bfloat16)
    tiles = jnp.zeros((2,), jnp.int32)
    jax.eval_shape(lambda lhs, w: grouped_matmul(
        lhs, w, tiles, jnp.ones((1,), jnp.int32),
        jnp.full((2,), 128, jnp.int32), 128), lhs, w)


def _call_resolve(hidden):
    from byteps_tpu.ops.routed_rows import resolve
    resolve("auto", 512, hidden, 256, 4, 128)


def _call_routed_act(width):
    from byteps_tpu.ops.routed_act import routed_act
    jax.eval_shape(lambda h: routed_act(h, jnp.ones((1,), jnp.int32), 128,
                                        "gated_silu"),
                   jnp.zeros((256, 2 * width), jnp.bfloat16))


def _call_embed_grad(hidden):
    from byteps_tpu.models.transformer import embed_grad
    jax.eval_shape(lambda ids, ct: embed_grad(ids, ct, 1000),
                   jnp.zeros((256,), jnp.int32),
                   jnp.zeros((256, hidden), jnp.bfloat16))


def _call_exchange(reducer):
    from byteps_tpu.parallel.collectives import tree_allreduce
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    from jax.sharding import PartitionSpec as P
    kwargs = {} if reducer is None else {"reducer": reducer}
    jax.eval_shape(jax.shard_map(
        lambda x: tree_allreduce({"a": x}, ("data",), **kwargs),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False),
        jnp.ones((8,), jnp.float32))


def _flat_psum(x, axes):
    return jax.lax.psum(x, axes)


# (site, the call, what it took on a TPU, is that a fall-back)
CHOICES = [
    ("attention", lambda: _call_attention((1, 128, 2, 64)), "flash", False),
    ("attention", lambda: _call_attention((1, 65, 2, 8)), "xla", True),
    ("flash_blocks", lambda: _call_flash(2048), "1024x1024", False),
    ("flash_blocks", lambda: _call_flash(512), "512x512", False),
    ("flash_blocks", lambda: _call_flash(2048, block_q=256), "256x1024",
     False),
    ("ssd", lambda: _call_ssd("plain"), "kernels", False),
    ("ssd", lambda: _call_ssd("packed"), "kernels_packed", False),
    ("ssd", lambda: _call_ssd("odd"), "xla", True),
    ("grouped_matmul", lambda: _call_grouped_matmul(128), "gmm", False),
    ("grouped_matmul", lambda: _call_grouped_matmul(100), "ragged", True),
    ("routed_rows", lambda: _call_resolve(256), "gmm", False),
    ("routed_rows", lambda: _call_resolve(100), "ragged", True),
    ("routed_act", lambda: _call_routed_act(128), "kernels", False),
    ("routed_act", lambda: _call_routed_act(192), "xla", True),
    ("embed_bwd", lambda: _call_embed_grad(128), "kernels", False),
    ("embed_bwd", lambda: _call_embed_grad(96), "xla", True),
    ("exchange", lambda: _call_exchange(None), "leaves", False),
    ("exchange", lambda: _call_exchange(_flat_psum), "buckets", False),
]


@pytest.mark.parametrize("site,call,took,fell_back", CHOICES, ids=[
    f"{site}-{took}" for site, _, took, _ in CHOICES])
def test_note_choice_counts_each_site_and_says_a_fall_back_once(
        monkeypatch, site, call, took, fell_back):
    warned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(setup_record, "_warned", set())
    monkeypatch.setattr(setup_record.get_logger(), "warning",
                        lambda *a: warned.append(a))
    rec = setup_record.open_record()
    try:
        call()
        call()
    finally:
        setup_record.close(rec)
    assert rec["choices"][site, took] == 2
    assert set(rec["choices"]) == {(site, took)}
    if fell_back:
        (key, count), = rec["fallbacks"].items()
        assert key[:2] == (site, took) and count == 2
        assert len(warned) == 1 and "falls back" in warned[0][0]
        assert warned[0][1:4] == (site, key[2], took)
    else:
        assert not rec["fallbacks"] and not warned


@pytest.mark.parametrize("asked,backend,falls", [
    ("auto", "tpu", True), ("gmm", "tpu", True), ("ragged", "tpu", False),
    ("naive", "tpu", False), ("auto", "cpu", False)])
def test_a_fall_back_is_xlas_form_on_a_tpu_that_nobody_asked_for(
        monkeypatch, asked, backend, falls):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(setup_record, "_warned", set())
    rec = setup_record.open_record()
    try:
        setup_record.note_choice("site", "ragged", (8, 100), "why",
                                 asked=asked)
    finally:
        setup_record.close(rec)
    assert rec["choices"]["site", "ragged"] == 1
    assert bool(rec["fallbacks"]) == falls


def test_ten_thousand_steps_add_nothing_to_the_record():
    """From its second call on ``step`` is the class's own: no span, no
    entry, no growth, and the listener is not called at all."""
    import copy
    trainer = _trainer()
    batch = trainer.shard_batch(_batch())
    trainer.step(batch)
    trainer.step(batch)
    rec = trainer.setup_record()
    before = copy.deepcopy(rec)
    calls = []

    def listen(*event, **_):        # beside the record's own listener
        calls.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for _ in range(10_000):
            loss = trainer.step(batch)
        jax.block_until_ready(loss)
    finally:
        jax._src.monitoring.unregister_event_duration_listener(listen)
    assert rec == before and not calls
    assert type(trainer).step is DistributedTrainer.step
    assert "step" not in vars(trainer)
