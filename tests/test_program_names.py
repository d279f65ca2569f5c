"""The names the program gives its own work (PERF.md section 3): scopes
on the step's phases, the model's parts and the exchange, ``name=`` on
the flash kernels, and the trainer's and the feed's host spans in the
profiler's trace. CPU only: what the names cost and read on the chip is
the benchmark's business (``benchmark/trace/program.py``)."""

import glob
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.data import prefetch_to_mesh
from byteps_tpu.models import bert, decoder, gpt2, transformer
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.training import DistributedTrainer

# every scope of the table in ISSUE 25 section 1 that a CPU lowering can
# hold (the kernels' own names need the Mosaic path: see the jaxpr tests)
SCOPES = ("bps.model", "bps.optimizer", "bps.exchange",
          "bps.exchange.reduce", "bps.embed",
          "bps.attn", "bps.mlp", "bps.head", "bps_attn_xla")
# the exchange's parts that only a bucketed step opens (a custom reducer,
# a dcn mesh, compression: ISSUE 37); the default ICI path reduces the
# leaves as they are, under bps.exchange / bps.exchange.reduce alone
BUCKET_SCOPES = ("bps.exchange.pack", "bps.exchange.unpack")
# what the decoder of several kinds of layer adds (ISSUE 29 section 5):
# the routed feed-forward's parts inside bps.mlp
MOE_SCOPES = ("bps.moe", "bps.moe.route", "bps.moe.experts",
              "bps.moe.shared")
# what the decoder's one-mixer layers add (ISSUE 34): a state-space mixer
# and its parts; its attention and routed layers keep bps.attn, bps.mlp
SSM_SCOPES = ("bps.ssm", "bps.ssm.proj", "bps.ssm.conv", "bps.ssm.scan",
              "bps.ssm.norm")


def _flat_psum(x, axes):
    return jax.lax.psum(x, axes)


def _trainer(model: str, mesh):
    if model == "bert_tiny_buckets":
        # a custom reducer takes (flat buffer, axes): the bucketed form
        cfg = bert.bert_tiny()
        loss = lambda p, b: bert.mlm_loss(p, cfg, b, max_predictions=8)  # noqa: E731
        make = lambda rng: bert.synth_mlm_batch(rng, 8, 32, 128)  # noqa: E731
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        return DistributedTrainer(loss, params, optax.adamw(1e-3), mesh=mesh,
                                  partition_bytes=1 << 16,
                                  reducer=_flat_psum), make
    if model == "bert_tiny":
        cfg = bert.bert_tiny()
        loss = lambda p, b: bert.mlm_loss(p, cfg, b, max_predictions=8)  # noqa: E731
        make = lambda rng: bert.synth_mlm_batch(rng, 8, 32, 128)  # noqa: E731
    elif model in ("afmoe_tiny", "nemotron_h_tiny"):
        cfg = getattr(decoder, model)()
        loss = lambda p, b: decoder.causal_lm_loss(p, cfg, b)  # noqa: E731
        make = lambda rng: gpt2.synth_lm_batch(rng, 8, 32, 128)  # noqa: E731
        params = decoder.init_params(jax.random.PRNGKey(0), cfg)
        return DistributedTrainer(loss, params, optax.adamw(1e-3), mesh=mesh,
                                  partition_bytes=1 << 16), make
    else:
        cfg = gpt2.gpt2_tiny()
        loss = lambda p, b: gpt2.causal_lm_loss(p, cfg, b)  # noqa: E731
        make = lambda rng: gpt2.synth_lm_batch(rng, 8, 32, 128)  # noqa: E731
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    # small buckets, so that the exchange has more than one of them
    trainer = DistributedTrainer(loss, params, optax.adamw(1e-3), mesh=mesh,
                                 partition_bytes=1 << 16)
    return trainer, make


@pytest.fixture(scope="module")
def lowered():
    """The step's lowering with its locations, once a model."""
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    texts = {}
    for model in ("bert_tiny_buckets", "bert_tiny", "afmoe_tiny",
                  "nemotron_h_tiny", "gpt2_tiny"):
        trainer, make = _trainer(model, mesh)
        step = trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                      make(np.random.RandomState(0)))
        texts[model] = step.as_text(debug_info=True)
        if model == "bert_tiny_buckets":
            texts["bucket_paths"] = set(re.findall(
                r'op_name="([^"]*)"', step.compile().as_text()))
    # the last model's compiled module: its op_name metadata holds the
    # whole scope path of an instruction, as a trace of the chip does
    texts["paths"] = set(re.findall(r'op_name="([^"]*)"',
                                    step.compile().as_text()))
    return texts


@pytest.mark.parametrize("model,scope", list(itertools.product(
    ("bert_tiny", "gpt2_tiny"), SCOPES)) + [
        ("afmoe_tiny", scope) for scope in SCOPES + MOE_SCOPES] + [
        ("nemotron_h_tiny", scope)
        for scope in SCOPES + MOE_SCOPES + SSM_SCOPES])
def test_lowered_step_holds_the_scope(lowered, model, scope):
    names = set(re.findall(r"bps[._][A-Za-z_.]+", lowered[model]))
    assert scope in names


@pytest.mark.parametrize("model,scope,there", [
    ("bert_tiny_buckets", scope, True)
    for scope in ("bps.exchange", "bps.exchange.reduce") + BUCKET_SCOPES] + [
    (model, scope, False) for model in (
        "bert_tiny", "gpt2_tiny", "afmoe_tiny", "nemotron_h_tiny")
    for scope in BUCKET_SCOPES])
def test_exchange_scopes_follow_its_form(lowered, model, scope, there):
    """``.pack`` / ``.unpack`` where buckets run, and only there."""
    names = set(re.findall(r"bps[._][A-Za-z_.]+", lowered[model]))
    assert (scope in names) == there


def test_scopes_nest_as_the_phases_do(lowered):
    paths = lowered["paths"]

    def some(pattern):
        return any(re.search(pattern, p) for p in paths)

    # forward, backward (transposed) and the exchange's parts each under
    # their phase's scope, and the phases beside each other
    assert some(r"bps\.model/jvp\(\)/.*bps\.attn")
    assert some(r"bps\.model/transpose\(.*bps\.mlp")
    assert some(r"bps\.model/.*jvp\(bps\.head\)")
    assert some(r"bps\.exchange/bps\.exchange\.reduce")
    assert not some(r"bps\.exchange\.(pack|unpack)")
    assert any(re.search(r"bps\.exchange/bps\.exchange\.pack", p)
               for p in lowered["bucket_paths"])
    assert some(r"shard_map/bps\.optimizer/")
    assert not some(r"bps\.optimizer/.*bps\.exchange")
    assert not some(r"bps\.model/.*bps\.optimizer")


def test_the_routed_layers_scopes_nest_inside_the_feed_forward():
    """``bps.moe`` lies inside ``bps.mlp``; routing, the experts' products
    and the shared expert inside it; forward and backward alike (the
    gathers' hand-written transposes keep the scope of their forward)."""
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer, make = _trainer("afmoe_tiny", mesh)
    step = trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                  make(np.random.RandomState(0)))
    paths = set(re.findall(r'op_name="([^"]*)"', step.compile().as_text()))

    def some(pattern):
        return any(re.search(pattern, p) for p in paths)

    for inner in ("route", "experts", "shared"):
        assert some(rf"bps\.model/jvp\(bps\.mlp\)/bps\.moe/bps\.moe\.{inner}")
        assert some(rf"bps\.model/transpose\(.*bps\.moe\.{inner}")
    assert not some(r"bps\.attn/.*bps\.moe")


def test_a_state_space_mixers_parts_nest_inside_it():
    """``bps.ssm`` holds the mixer's norm and its four parts, forward and
    backward; the routed layer of a one-mixer decoder lies in ``bps.mlp``
    as afmoe's does, and nothing of it in ``bps.ssm``."""
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer, make = _trainer("nemotron_h_tiny", mesh)
    step = trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                  make(np.random.RandomState(0)))
    paths = set(re.findall(r'op_name="([^"]*)"', step.compile().as_text()))

    def some(pattern):
        return any(re.search(pattern, p) for p in paths)

    for inner in ("proj", "conv", "scan", "norm"):
        assert some(rf"bps\.model/jvp\(bps\.ssm\)/bps\.ssm\.{inner}")
        assert some(rf"bps\.model/transpose\(.*bps\.ssm\.{inner}")
    assert some(r"bps\.model/jvp\(bps\.mlp\)/bps\.moe/bps\.moe\.experts")
    assert some(r"bps\.model/jvp\(\)/.*bps\.attn|jvp\(bps\.attn\)")
    assert not some(r"bps\.ssm/.*bps\.moe") and not some(
        r"bps\.attn/.*bps\.ssm")


SSM_KERNELS = {     # stage -> (forward call, its kernel, backward call, ...)
    "conv": ("_conv_fwd_call", "bps_ssm_conv_fwd",
             "_conv_bwd_call", "bps_ssm_conv_bwd"),
    "norm": ("_norm_fwd_call", "bps_ssm_norm_fwd",
             "_norm_bwd_call", "bps_ssm_norm_bwd"),
}


@pytest.fixture(scope="module")
def ssm_kernels_step():
    """A checkpointed state-space layer's step at shapes the TPU's
    kernels take (a hidden size of one lane tile: the embedding's backward
    too), traced as on a TPU and lowered FOR one (no chip: the lowering
    ends in Mosaic's serialised kernels): its text with locations, its
    call sites' scope paths by callee, its set-up record."""
    from byteps_tpu.ops import mamba2_kernels
    cfg = decoder.nemotron_h_tiny(
        hidden=128, ssm_head_dim=64, ssm_state=128, chunk=128, remat=True,
        layer_kinds=("ssm", "ssm"), dtype="bfloat16")
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        trainer = DistributedTrainer(
            lambda p, b: decoder.causal_lm_loss(p, cfg, b), params,
            optax.adamw(1e-3), mesh=mesh)
        batch = jax.ShapeDtypeStruct((1, mamba2_kernels.ROWS[-1]),
                                     jnp.int32)
        text = trainer._step_fn.trace(
            trainer.params, trainer.opt_state, batch).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        jax.default_backend = real
    from byteps_tpu.common import setup_record
    setup_record.close(trainer.setup_record())
    named = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
    sites = {}
    for callee, loc in re.findall(r"call @(\w+?)(?:_\d+)?\(.*loc\((#loc\d+)\)",
                                  text):
        sites.setdefault(callee, []).append(named.get(loc, ""))
    return text, sites, trainer.setup_record()


@pytest.mark.parametrize("stage", sorted(SSM_KERNELS))
def test_the_stages_beside_the_scan_are_kernels_under_their_scopes(
        ssm_kernels_step, stage):
    """Forward, recompute and backward: every call of a stage's two
    kernels lies under ``bps.ssm.<stage>`` inside ``bps.model``, none
    under an empty path, and each kernel is lowered once for the two
    layers."""
    text, sites, _ = ssm_kernels_step
    fwd_call, fwd, bwd_call, bwd = SSM_KERNELS[stage]
    scope = rf"bps\.ssm/bps\.ssm\.{stage}/jit\({{}}\)$"
    fwd_sites, bwd_sites = sites[fwd_call], sites[bwd_call]
    assert len(fwd_sites) == 4 and len(bwd_sites) == 2      # two layers
    forward = [p for p in fwd_sites if "transpose(" not in p]
    recompute = [p for p in fwd_sites if "rematted_computation" in p]
    assert len(forward) == 2 and len(recompute) == 2
    for p in forward:
        assert re.search(r"bps\.model/jvp\(bps\.ssm\)/bps\.ssm\."
                         rf"{stage}/jit\({fwd_call}\)$", p), p
    for p in recompute:
        assert re.search(r"bps\.model/transpose\(.*rematted_computation/"
                         + scope.format(fwd_call), p), p
    for p in bwd_sites:
        assert "rematted_computation" not in p and re.search(
            r"bps\.model/transpose\(.*checkpoint/" + scope.format(bwd_call),
            p), p
    for call, kernel in ((fwd_call, fwd), (bwd_call, bwd)):
        assert len(re.findall(rf"func\.func private @{call}\(", text)) == 1
        assert f'loc("{kernel}/pallas_call' in text
        assert f'kernel_name = "{kernel}"' in text


def test_the_set_up_record_holds_the_state_space_sites(ssm_kernels_step):
    _, _, rec = ssm_kernels_step
    chose = {k: v for k, v in rec["choices"].items() if k[0] != "exchange"}
    assert chose == {("ssm_conv", "kernels"): 2, ("ssm_norm", "kernels"): 2,
                     ("ssd", "kernels_packed"): 2,
                     ("embed_bwd", "kernels"): 1}
    assert not rec["fallbacks"]


def test_the_embeddings_backward_is_a_kernel_under_its_scope(
        ssm_kernels_step):
    """``bps_embed_dw`` once a step, in the backward phase under
    ``bps.embed``, outside every layer's checkpoint: a trace's reader
    finds the op by that scope."""
    text, sites, _ = ssm_kernels_step
    path, = sites["embed_dw"]
    assert re.search(r"bps\.model/transpose\(bps\.model\)/"
                     r"jvp\(bps\.embed\)/jit\(embed_dw\)$", path), path
    assert "checkpoint" not in path and "rematted" not in path
    assert 'loc("bps_embed_dw/pallas_call' in text
    assert 'kernel_name = "bps_embed_dw"' in text


@pytest.mark.parametrize("seq,kernels", [
    (128, ("bps_flash_fwd", "bps_flash_bwd_fused")),
    (256, ("bps_flash_fwd", "bps_flash_bwd_dq", "bps_flash_bwd_dkv")),
], ids=["one_block_fused", "two_blocks_split"])
def test_flash_kernels_carry_their_names(seq, kernels):
    from byteps_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128,
                               True).astype(jnp.float32).sum()

    x = jnp.zeros((1, seq, 2, 64), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    assert set(re.findall(r"name=(bps_flash_\w+)", jaxpr)) == set(kernels)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name == "/host:CPU")
    return [(e.name, dict(e.stats)) for line in plane.lines
            for e in line.events if e.name.startswith("bps.")]


def test_host_spans_land_in_the_profilers_trace(tmp_path):
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer, make = _trainer("bert_tiny", mesh)
    rng = np.random.RandomState(0)
    feed = prefetch_to_mesh((make(rng) for _ in range(4)), mesh)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for batch in feed:
            loss = trainer.step(batch)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = [stats["step_num"] for name, stats in events
             if name == "bps.step"]
    assert steps == [0, 1, 2, 3]
    count = {name: sum(n == name for n, _ in events)
             for name in {n for n, _ in events}}
    assert count["bps.dispatch"] == count["bps.feed.h2d"] == 4
    assert count["bps.feed.source"] == 5       # the last finds the end
    assert count["bps.feed.wait"] >= 4
    assert count["bps.shard_batch"] == 4       # device batches: eager put
    host_bytes = sum(x.nbytes for x in make(rng))
    assert {stats["bytes"] for name, stats in events
            if name == "bps.feed.h2d"} == {host_bytes}


SETUP_SPANS = ("bps.setup.init", "bps.setup.place_params",
               "bps.setup.opt_init", "bps.setup.build_step",
               "bps.setup.first_step")


@pytest.fixture(scope="module")
def setup_events(tmp_path_factory):
    """A profiler session an operator starts BEFORE building the trainer:
    the set-up record's spans are host spans of that trace as well."""
    trace_dir = str(tmp_path_factory.mktemp("setup_trace"))
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        trainer, make = _trainer("bert_tiny", mesh)
        batch = make(np.random.RandomState(0))
        trainer.step(batch)
        jax.block_until_ready(trainer.step(batch))
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir), trainer


@pytest.mark.parametrize("name", SETUP_SPANS)
def test_setup_spans_land_in_the_profilers_trace(setup_events, name):
    """Once each, with the arguments the record holds; ``first_step``
    shares ``bps.step``'s identifier and the second step has none."""
    events, trainer = setup_events
    stats, = [stats for n, stats in events if n == name]
    recorded, = [s for s in trainer.setup_record()["spans"]
                 if s["name"] == name]
    assert {k: stats[k] for k in recorded["args"]} == recorded["args"]
    if name == "bps.setup.first_step":
        assert stats["step_num"] == 0
        assert [st["step_num"] for n, st in events
                if n == "bps.step"] == [0, 1]


class _CountingTime:
    """``time`` with its clock calls counted."""

    def __init__(self):
        import time
        self._time, self.calls = time, 0

    def __getattr__(self, name):
        value = getattr(self._time, name)
        if callable(value):
            def counted(*a, **kw):
                self.calls += 1
                return value(*a, **kw)
            return counted
        return value


@pytest.mark.parametrize("stats_on,calls_a_step", [(True, 2), (False, 0)])
def test_no_session_no_clock(monkeypatch, stats_on, calls_a_step):
    """With no profiler session a step reads the clock as often as it
    did before the spans: twice for ``StepStats`` where ``bps.init`` ran,
    never otherwise."""
    import byteps_tpu as bps
    from byteps_tpu import training
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    if stats_on:
        bps.init(mesh=mesh)
    trainer, make = _trainer("bert_tiny", mesh)
    batch = make(np.random.RandomState(0))
    trainer.step(batch)
    clock = _CountingTime()
    monkeypatch.setattr(training, "time", clock)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    for _ in range(3):
        loss = trainer.step(batch)
    jax.block_until_ready(loss)
    assert clock.calls == 3 * calls_a_step
