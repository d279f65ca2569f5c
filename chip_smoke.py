"""The quickest proof that byteps_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one host with four (data parallel only)

Drives the main path through the entry points a user calls, at the full
width of BERT-large (batch 64 x seq 512 per chip, bf16, random weights
from a seed), and checks what comes out against a plain-JAX step of the
same model. One process, one JSON object per phase on standard output;
the LAST line is ``{"ok": true, "device": {...}}`` with the device as
JAX reported it. Any failed phase raises: non-zero exit, no ok line.
Without a TPU it fails in the ``device`` phase — it never continues on
the CPU. Times printed here are smoke figures, not a benchmark.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import re
import statistics
import time

BATCH_PER_CHIP, SEQ = 64, 512
TRAIN_STEPS, PS_STEPS, DP_STEPS = 5, 3, 3
# Step-by-step loss of the framework arm against the plain-JAX arm,
# relative. Chosen from the first chip runs (PR 22): on one chip the two
# arms were bit-equal over all 5 steps (and so was the PS arm); at dp=4
# they differed by at most 2.9e-6 (327 bucketed psums against one tree
# pmean: another summation order). 1e-4 is ~30x that, and two orders
# below what one dropped or doubled gradient bucket does in one step.
LOSS_RTOL = 1e-4


def model_config():
    from byteps_tpu.models import bert
    return bert.bert_large(max_seq=SEQ)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_flash(hlo_text: str, what: str) -> None:
    """The Pallas flash kernel is IN the compiled program — attention()
    did not take its naive O(s^2) branch, and nothing ran interpreted."""
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled "
                             f"program — the flash kernel is not in it")


def require_tpu(devs, chips: int) -> None:
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) != chips:
        raise SystemExit(f"chip_smoke: wants {chips} chip(s), JAX found "
                         f"{len(devs)} (see --chips)")


def require_no_interpret() -> None:
    from byteps_tpu.ops.compression import pallas_kernels as pk
    if pk._interpret():
        raise AssertionError("compression kernels chose interpret mode")


def device_memory() -> dict:
    """``peak_bytes_in_use`` counts live buffers; the step program's own
    temporaries show in the ``*_reserved`` figures where the backend
    reports them, so all of them go on the line."""
    import jax
    stats = jax.devices()[0].memory_stats()
    return {k: stats[k] for k in (
        "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved",
        "largest_alloc_size", "bytes_limit") if k in stats}


def check_losses(name, got, want, falls=True) -> float:
    import math
    if not all(math.isfinite(x) for x in list(got) + list(want)):
        raise AssertionError(f"{name}: non-finite loss {got} vs {want}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(got, want))
    if worst > LOSS_RTOL:
        raise AssertionError(
            f"{name}: losses {got} differ from the plain-JAX arm's {want} "
            f"by {worst:.2e} relative (tolerance {LOSS_RTOL})")
    if falls and not got[-1] < got[0]:
        raise AssertionError(f"{name}: loss did not fall: {got}")
    return worst


def phase_device(chips: int) -> dict:
    import jax
    import jaxlib
    from importlib.metadata import version

    from byteps_tpu.common.config import enable_compile_cache
    from byteps_tpu.models.flops import chip_peak_flops

    cache = enable_compile_cache()
    devs = jax.devices()
    require_tpu(devs, chips)
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    emit("device", **device,
         peak_bf16_tflops=chip_peak_flops(d) / 1e12,   # unknown kind raises
         jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=version("libtpu"), compile_cache=cache,
         compile_cache_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)
    return device


def phase_kernels() -> None:
    """On-chip numerics of every Pallas kernel: flash fwd/bwd and ring
    against naive attention, the compression kernels byte-for-byte
    against the host codecs. Nothing interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from byteps_tpu.compress import device as cdev
    from byteps_tpu.ops.compression import pallas_kernels as pk
    from byteps_tpu.ops.flash_attention import flash_attention

    t0 = time.perf_counter()
    require_no_interpret()
    q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
    require_flash(
        jax.jit(jax.grad(lambda q, k, v: flash_attention(q, k, v)
                         .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        .lower(q, q, q).as_text(), "flash_attention fwd+bwd")
    bench.verify_kernels()          # flash fwd/bwd + ring; raises
    cdev._probe()                   # int8, fp8 e4m3/e5m2; raises by name

    n = 100_003                     # not a multiple of the 32-bit pack
    chunks = (n + pk.PACK - 1) // pk.PACK
    x = np.random.RandomState(3).randn(n).astype(np.float32)
    words = pk.onebit_pack(jnp.asarray(x), chunks)
    host = np.packbits(np.pad(x < 0, (0, chunks * pk.PACK - n)),
                       bitorder="big").view(">u4")
    if not np.array_equal(np.asarray(words).astype(np.uint32), host):
        raise AssertionError("onebit_pack differs from the host packing")
    if not np.array_equal(np.asarray(pk.onebit_unpack(words, n)),
                          np.where(x < 0, -1.0, 1.0)):
        raise AssertionError("onebit_unpack does not invert the packing")
    emit("kernels", flash_fwd_bwd="ok", ring="ok",
         codecs_byte_identical=["onebit"] + [
             cdev.wire.codec_name(c) for c in cdev.DEVICE_CODECS],
         interpret=False, seconds=round(time.perf_counter() - t0, 2))


def _timed_steps(step, n: int):
    """Run ``step()`` n times. Per step: seconds until the call returned,
    until ``jax.block_until_ready(loss)`` returned, and until the host
    read the loss back — if block_until_ready waits for the device, the
    last two agree and the first is only the enqueue."""
    import jax
    losses, rows = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step()
        t1 = time.perf_counter()
        jax.block_until_ready(loss)
        t2 = time.perf_counter()
        losses.append(float(loss))
        rows.append((t1 - t0, t2 - t0, time.perf_counter() - t0))
    return losses, [statistics.median(c) for c in zip(*rows)]


def _trainer_arm(trainer, batch, steps: int, what: str):
    """Compile the trainer's step (timed, flash kernel required), run
    ``steps`` steps on one fixed batch, free the trainer's state."""
    import jax
    t0 = time.perf_counter()
    text = trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                  batch).compile().as_text()
    compile_s = time.perf_counter() - t0
    require_flash(text, what)
    first = float(trainer.step(batch))
    losses, (enq, blk, rb) = _timed_steps(lambda: trainer.step(batch),
                                          steps - 1)
    memory = device_memory()
    trainer.params = trainer.opt_state = None
    gc.collect()
    return text, [first] + losses, {
        "compile_s": round(compile_s, 2),
        "step_s_enqueue": enq, "step_s_block_until_ready": blk,
        "step_s_readback": rb,
        "block_until_ready_waits": rb - blk < 0.05 * rb,
        **memory}


def _plain_arm(step, params, state, batch, steps: int):
    t0 = time.perf_counter()
    params, state, loss = step(params, state, batch)
    first = float(loss)
    first_s = time.perf_counter() - t0
    losses = [first]
    rows = []
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
        rows.append(time.perf_counter() - t0)
    del params, state
    gc.collect()
    return losses, {"plain_first_step_s": round(first_s, 2),
                    "plain_step_s_readback": statistics.median(rows)}


def phase_train() -> float:
    """The main path: bps.init() -> DistributedTrainer.step, against the
    plain-JAX step from the same seed on the same batch. The two states
    do not fit the chip together, so the arms run one after the other."""
    import optax

    import bench
    import byteps_tpu as bps
    from byteps_tpu.training import DistributedTrainer

    cfg = model_config()
    bps.init()
    params, data, loss_fn = bench.mlm_setup(cfg, BATCH_PER_CHIP, SEQ)
    trainer = DistributedTrainer(loss_fn, params, optax.adamw(1e-4))
    del params
    gc.collect()
    _, losses, stats = _trainer_arm(trainer, data, TRAIN_STEPS,
                                    "DistributedTrainer step")
    del trainer

    params, _, _ = bench.mlm_setup(cfg, BATCH_PER_CHIP, SEQ)
    tx = optax.adamw(1e-4)
    plain, pstats = _plain_arm(bench.make_plain_step(loss_fn, tx), params,
                               tx.init(params), data, TRAIN_STEPS)
    del params
    worst = check_losses("train", losses, plain)
    emit("train", model="bert_large", layers=cfg.layers,
         batch=BATCH_PER_CHIP, seq=SEQ, dtype=cfg.dtype, steps=TRAIN_STEPS,
         losses=losses, plain_losses=plain, max_rel_diff=worst,
         rtol=LOSS_RTOL, flash_kernel_in_step=True,
         smoke_samples_per_s=BATCH_PER_CHIP
         / stats["step_s_block_until_ready"], **stats, **pstats)
    return losses[0]


class _StagedLog(logging.Handler):
    """Keeps what staged_grad/training say about the staged head — the
    fallback reason is an INFO log line there; here it goes on the
    phase's line."""

    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.lines = []

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("staged"):
            self.lines.append(msg)


def phase_ps(train_loss1: float) -> None:
    """The parameter-server step, same width: re-init in this process
    with BPS_ENABLE_PS=1 (world 1, in-process backend, the native
    server library built here from the committed sources)."""
    import optax

    import bench
    import byteps_tpu as bps
    from byteps_tpu.common.logging import get_logger
    from byteps_tpu.obs.metrics import get_registry
    from byteps_tpu.training import DistributedTrainer

    bps.shutdown()
    os.environ["BPS_ENABLE_PS"] = "1"   # read by Config.from_env() in init
    bps.init()
    cfg = model_config()
    params, data, loss_fn = bench.mlm_setup(cfg, BATCH_PER_CHIP, SEQ)
    trainer = DistributedTrainer(loss_fn, params, optax.adamw(1e-4))
    del params
    gc.collect()
    staged_log = _StagedLog()
    get_logger().addHandler(staged_log)
    reg = get_registry()
    reg.reset()
    losses, secs = [], []
    for _ in range(PS_STEPS):
        t0 = time.perf_counter()
        losses.append(float(trainer.step(data)))
        secs.append(time.perf_counter() - t0)
    trainer.drain()
    get_logger().removeHandler(staged_log)
    check_losses("ps", losses[:1], [train_loss1], falls=False)
    if not losses[-1] < losses[0]:
        raise AssertionError(f"ps: loss did not fall: {losses}")
    snap = reg.snapshot()
    stages = reg.stage_totals()
    staged = trainer._staged
    trainer.close()
    bps.shutdown()
    emit("ps", model="bert_large", layers=cfg.layers, batch=BATCH_PER_CHIP,
         seq=SEQ, steps=PS_STEPS, losses=losses,
         step1_rel_diff_vs_train=abs(losses[0] - train_loss1)
         / abs(train_loss1),
         head="staged" if staged else "monolithic",
         segments=staged.n_segments if staged else 1,
         staged_builds=snap.get("staged/builds", 0),
         staged_build_fallback=snap.get("staged/build_fallback", 0),
         staged_log=staged_log.lines,
         first_step_s=round(secs[0], 2), warm_step_s=min(secs[1:]),
         d2h_bytes_per_step=snap.get("ps/d2h_bytes", 0) // PS_STEPS,
         h2d_bytes_per_step=snap.get("ps/pull_bytes", 0) // PS_STEPS,
         **{f"{s.lower()}_span_s_per_step": stages[s][1] / PS_STEPS
            for s in ("PS_D2H", "PS_H2D", "PS_PUSH_PULL") if s in stages})


def phase_dp(chips: int) -> None:
    """Data parallel across the chips of one host: the trainer's
    exchange on ICI (one psum a gradient leaf, the default path) against
    one plain jitted step on the same mesh (per-shard grads, one tree
    pmean — XLA's own all-reduce)."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    import byteps_tpu as bps
    from byteps_tpu.training import DistributedTrainer

    cfg = model_config()
    batch = BATCH_PER_CHIP * chips
    bps.init()                      # default mesh: every device on `data`
    params, data, loss_fn = bench.mlm_setup(cfg, batch, SEQ)
    trainer = DistributedTrainer(loss_fn, params, optax.adamw(1e-4))
    del params
    gc.collect()
    mesh = trainer.mesh
    if dict(mesh.shape) != {"data": chips}:
        raise AssertionError(f"default mesh is {dict(mesh.shape)}")
    for leaf in jax.tree_util.tree_leaves(trainer.params):
        if len(leaf.sharding.device_set) != chips:
            raise AssertionError("a param leaf is not on every chip: "
                                 f"{leaf.sharding}")
    dbatch = trainer.shard_batch(data)
    for a in jax.tree_util.tree_leaves(dbatch):
        rows = {s.device.id: s.data.shape[0] for s in a.addressable_shards}
        if rows != {d.id: BATCH_PER_CHIP for d in jax.devices()}:
            raise AssertionError(f"batch rows per device: {rows}")
    text, losses, stats = _trainer_arm(trainer, dbatch, DP_STEPS,
                                       "dp DistributedTrainer step")
    del trainer
    all_reduces = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    if not all_reduces:
        raise AssertionError("no all-reduce in the compiled dp step")

    tx = optax.adamw(1e-4)

    def pstep(p, s, b):
        l, g = jax.value_and_grad(loss_fn)(p, b)
        g, l = jax.lax.pmean((g, l), "data")
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    plain_step = jax.jit(jax.shard_map(
        pstep, mesh=mesh, in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P()), check_vma=False), donate_argnums=(0, 1))
    rep = NamedSharding(mesh, P())
    params = jax.device_put(bench.mlm_setup(cfg, batch, SEQ)[0], rep)
    plain, pstats = _plain_arm(plain_step, params,
                               jax.device_put(tx.init(params), rep),
                               dbatch, DP_STEPS)
    del params
    worst = check_losses("dp", losses, plain)
    bps.shutdown()
    emit("dp", model="bert_large", layers=cfg.layers, mesh=dict(mesh.shape),
         global_batch=batch, batch_per_chip=BATCH_PER_CHIP, seq=SEQ,
         steps=DP_STEPS, params_on_devices=chips, losses=losses,
         plain_losses=plain, max_rel_diff=worst, rtol=LOSS_RTOL,
         all_reduces_in_step=all_reduces, flash_kernel_in_step=True,
         smoke_samples_per_s=batch / stats["step_s_block_until_ready"],
         **stats, **pstats)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel phase across the "
                         "four chips of one host (default: 1 chip, the "
                         "kernels, train and ps phases)")
    chips = ap.parse_args(argv).chips
    device = phase_device(chips)
    if chips == 1:
        phase_kernels()
        phase_ps(phase_train())
    else:
        phase_dp(chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
