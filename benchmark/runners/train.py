"""The training runner: drives `train/loop.build`'s compiled step over
batches made from the seed, as `bench.py` does (`bench.py:438-470`, the loop
`loop.build` -> batch -> `train_step`; nothing else of it is copied).

Set-up builds ONE compiled step with its state, puts the benchmark's own
weights (made on the device from the seed by the configuration's reference
file) into that state, and drives it through its first three steps; the same
object then runs the window. After the window the program's state is freed and
the plain reference follows those three steps (`train_check.py`).
"""

from __future__ import annotations

import collections
import gc
import sys
import time

from benchmark import harness, train_check
from benchmark.references import optim as ref_optim

TOTAL_STEPS = 1_000_000  # the schedule is constant; part of the AOT key
AHEAD = 2                # steps in flight before the host waits


def _name(path) -> str:
    """'/'-joined dictionary keys of a leaf's path. Attribute keys are the
    boxes flax puts round a partitioned leaf (`.value`) and are left out."""
    import jax
    return "/".join(str(k.key) for k in path
                    if isinstance(k, jax.tree_util.DictKey))


def _flat(tree) -> dict:
    import jax
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_name(p): v for p, v in leaves}


def _train_config(cell: dict, devices, rehearsal: bool):
    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, PrecisionPolicy,
        TrainConfig)

    prog = cell["config_file"]["train"]
    tr = cell["traffic_file"]
    opt = prog["optimizer"]
    policy = (PrecisionPolicy.mixed() if prog["precision"] == "mixed"
              else PrecisionPolicy.fp32())
    if "seq_len" in tr:
        data = DataConfig(synthetic=True, dataset="mlm",
                          seq_len=tr["seq_len"],
                          vocab_size=cell["config_file"]["vocab_size"])
    else:
        data = DataConfig(synthetic=True, image_size=tr["image_size"],
                          num_classes=cell["config_file"]["num_classes"])
    return TrainConfig(
        model=prog["model"], backend=None if rehearsal else "tpu",
        global_batch_size=tr["batch"] * len(devices), seed=0,
        dtype=policy.compute_dtype, precision=policy, log_every=10 ** 9,
        attention_impl=prog.get("attention_impl"),
        parallel=ParallelConfig(data=len(devices)), data=data,
        optimizer=OptimizerConfig(
            name=opt["name"], learning_rate=opt["learning_rate"],
            reference_batch=tr["batch"] * len(devices),
            momentum=opt.get("momentum", 0.9),
            weight_decay=opt["weight_decay"], schedule="constant",
            warmup_epochs=0.0,
            label_smoothing=opt.get("label_smoothing", 0.0),
            beta1=opt.get("beta1", 0.9), beta2=opt.get("beta2", 0.999),
            eps=opt.get("eps", 1e-8)))


def _install_weights(state, flat_params: dict, ref):
    """Put the benchmark's weights into the program's state, leaf for leaf.
    The two trees must agree in names, shapes and types."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(state.params)
    theirs = {_name(p): v for p, v in leaves}
    if set(theirs) != set(flat_params):
        odd = sorted(set(theirs) ^ set(flat_params))[:8]
        raise harness.CellError(f"reference and program disagree on the "
                                f"parameter names, e.g. {odd}")
    new = []
    for p, old in leaves:
        leaf = flat_params[_name(p)]
        if leaf.shape != old.shape or leaf.dtype != old.dtype:
            raise harness.CellError(
                f"parameter {_name(p)}: reference {leaf.shape} {leaf.dtype},"
                f" program {old.shape} {old.dtype}")
        # no copy: the step donates its state, and the runner keeps no
        # second set of the weights beside it
        new.append(jax.device_put(leaf, old.sharding))
    return state.replace(params=jax.tree_util.tree_unflatten(treedef, new))


def _moment(opt_state, field: str) -> dict:
    import jax
    nodes = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, field))
    for node in nodes:
        if hasattr(node, field):
            return _flat(getattr(node, field))
    raise harness.CellError(f"no {field!r} in the program's optimizer state")


def _note_memory(when: str, devices) -> None:
    stats = devices[0].memory_stats() or {}
    print(f"memory {when}: " + " ".join(
        f"{k}={stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_reserved", "peak_bytes_reserved")
        if k in stats), file=sys.stderr)


def prepare(cell: dict, args, devices, wrap_step=None):
    """Build the step and its state, install the seed's weights and take the
    checked steps. Returns everything the window needs."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.perf import compile_cache
    from distributeddeeplearning_tpu.train import loop

    compile_cache.activate()
    cfgfile, tr = cell["config_file"], cell["traffic_file"]
    ref = harness.load_module("references", cfgfile["reference"])
    sz = ref.sizes(cfgfile)
    opt = cfgfile["train"]["optimizer"]
    cfg = _train_config(cell, devices, args.rehearsal)
    mesh, model, batch_shd, state, train_step, _, _ = loop.build(
        cfg, TOTAL_STEPS)
    _note_memory("built", devices)
    if wrap_step is not None:  # tests plant faults here
        train_step = wrap_step(train_step)

    seed_key = jax.random.key(args.seed)
    rng = jax.random.fold_in(seed_key, 0x5EED)  # the dropout stream's key
    init = jax.jit(lambda k: ref.init_params(sz, k))
    state = _install_weights(state, init(seed_key), ref)
    global_traffic = dict(tr, batch=tr["batch"] * len(devices))
    gen = jax.jit(lambda k, i: ref.make_batch(global_traffic, sz, k, i),
                  out_shardings=batch_shd)

    diff_norms = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
    program = {"loss": []}
    for i in range(train_check.STEPS):
        state, metrics = train_step(state, gen(seed_key, jnp.int32(i)), rng)
        program["loss"].append(metrics["loss"])
        if i == 0:
            moment = _moment(state.opt_state, ref_optim.moment_field(opt))
            program["grad"] = ref_optim.first_gradient_norms(
                opt, ref.decays, moment, lambda: init(seed_key))
            del moment
    # The seed's weights are made again, by the program that made them (the
    # same bits), and live only until the norms are taken: kept across the
    # checked steps they would be 4 B a parameter beside the step's memory.
    program["change"] = diff_norms(_flat(state.params), init(seed_key))
    program = jax.device_get(program)
    _note_memory("three steps taken", devices)
    program = {"loss": [float(x) for x in program["loss"]],
               "grad": {k: float(v) for k, v in program["grad"].items()},
               "change": {k: float(v) for k, v in program["change"].items()}}
    return dict(state=state, train_step=train_step, gen=gen, rng=rng,
                seed_key=seed_key, program=program, ref=ref, sz=sz, opt=opt,
                traffic=global_traffic, next_step=train_check.STEPS)


def window(prep: dict, seconds: float, spans, tracer) -> dict:
    """Drive the step for `seconds`, a few steps in flight. All the steps
    dispatched are waited for and counted, over all the time that took."""
    import jax
    import jax.numpy as jnp

    state, train_step, gen = prep["state"], prep["train_step"], prep["gen"]
    rng, key = prep["rng"], prep["seed_key"]
    i = prep["next_step"]
    inflight = collections.deque()
    losses = []

    def drain():
        with spans("block"):
            while inflight:
                jax.block_until_ready(inflight.popleft())

    t0 = time.perf_counter()
    steps = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        tracer.poll(now, drain)
        with spans("batch"):
            batch = gen(key, jnp.int32(i))
        with spans("dispatch"):
            state, metrics = train_step(state, batch, rng)
        keep = {k: metrics[k] for k in ("loss", "loss_scale_skip")
                if k in metrics}
        inflight.append(keep)
        losses.append(keep)
        tracer.add(1)
        i += 1
        steps += 1
        if len(inflight) > AHEAD:
            with spans("block"):
                jax.block_until_ready(inflight.popleft())
    tracer.finish(drain)
    drain()
    elapsed = time.perf_counter() - t0
    got = jax.device_get(losses)
    failed = sum(1 for m in got
                 if not (m["loss"] == m["loss"] and abs(m["loss"]) < 1e30)
                 or m.get("loss_scale_skip", 0.0) > 0)
    prep["state"] = state
    return dict(steps=steps, elapsed=elapsed, failed=failed)


def check(prep: dict, limits: dict):
    """Free the program's state, run the reference, compare."""
    import jax

    program = prep["program"]
    ref, sz, opt = prep["ref"], prep["sz"], prep["opt"]
    seed_key, rng, traffic = prep["seed_key"], prep["rng"], prep["traffic"]
    for k in ("state", "train_step", "gen"):
        prep.pop(k, None)
    gc.collect()
    jax.clear_caches()
    reference = train_check.reference_readings(ref, sz, traffic, opt,
                                               seed_key, rng)
    numbers = train_check.compare(program, reference)
    ok, rows = train_check.verdict(numbers, limits)
    print(f"check: program losses {program['loss']} reference "
          f"{reference['loss']} widest at {numbers['at']} left out "
          f"{len(numbers['leaves_left_out'])} leaves", file=sys.stderr)
    return ok, rows, numbers


def run(cell: dict, args, devices, t_start: float, wrap_step=None):
    spans = harness.Spans()
    tracer = harness.Tracer(spans, bool(args.trace))
    prep = prepare(cell, args, devices, wrap_step)
    tr = cell["traffic_file"]
    setup_s = time.time() - t_start
    win = window(prep, args.seconds, spans, tracer)
    memory_peak = harness.memory_peak_bytes(devices)
    t_ref = time.time()
    print(f"phases: setup {setup_s:.1f}s window {win['elapsed']:.2f}s "
          f"steps {win['steps']}", file=sys.stderr)
    # where the host was: a window that reads far off says by this line
    # whether one call stalled (a longest far above the others) or every
    # step ran slow (a sum that grew with none standing out)
    held = {n: spans.durations(n) for n in ("batch", "dispatch", "block")}
    print("window: " + " ".join(
        f"{n} sum {sum(d):.3f}s longest {max(d, default=0.0):.3f}s"
        for n, d in held.items()) + f" outside them "
        f"{win['elapsed'] - sum(map(sum, held.values())):.3f}s",
        file=sys.stderr)
    _note_memory("window closed", devices)
    chips = len(devices)
    examples = win["steps"] * tr["batch"] * chips
    e2e = {"train_examples_per_s": examples / win["elapsed"] / chips,
           "setup_s": setup_s}
    reduced = tracer.reduced()
    ctx = dict(cell=cell, config=cell["config_file"], traffic=tr,
               spans=spans, trace=reduced, chips=chips,
               peaks=(harness.peaks_for(devices[0].device_kind)
                      if devices[0].platform == "tpu" else None),
               traced_units=tracer.units * tr["batch"] * chips,
               traced_s=tracer.stretch_s)
    ok, rows, _ = check(prep, tr["limits"])
    print(f"phases: reference and comparison {time.time() - t_ref:.1f}s",
          file=sys.stderr)
    _note_memory("reference done", devices)
    return harness.emit(cell, trace_on=bool(args.trace), e2e=e2e, ctx=ctx,
                        attempted=win["steps"], failed=win["failed"],
                        devices=devices, memory_peak=memory_peak,
                        checks=rows, correct=ok and win["failed"] == 0,
                        reduced=reduced)
