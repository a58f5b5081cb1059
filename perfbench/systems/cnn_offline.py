"""Drives the program's sparse CNN over offline batches.

The program is used through its normal entry points: its CNN config
(``repro.configs``) and ``SparseCNN.apply`` under ``jax.jit``. Weights
and images are the benchmark's own, drawn from the seed on the device.

A run: set-up (weights, images, ``warmup_steps`` steps, which compile or
load the step program), then the window: steps dispatched back to back
over the cycled batches, at most two in flight; the window ends when the
first step that completes at or after ``--seconds`` has completed and
the steps still in flight have finished. Every step's logits are kept
and, after the program's state is freed, each is compared with the
reference's logits of its batch.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, devtrace, device, gcwatch, weights, work
from harness import window as win
from harness.record import Outcome, Record, RunArgs
from harness.spec import SpecError

IN_FLIGHT = 2


def program_model(cfg: dict):
    """The program's SparseCNN for the configuration file, checked."""
    from repro.configs import get_cnn_config, get_cnn_reduced
    from repro.models.conv import SparseCNN

    get = get_cnn_config if cfg["program_preset"] == "full" else get_cnn_reduced
    mc = get(cfg["program_arch"])
    if mc.kind != "resnet":
        raise SpecError(f"{mc.name}: the reference is a bottleneck ResNet")
    have = {
        "input_hw": mc.input_hw, "num_classes": mc.num_classes,
        "stem_pool": mc.stem_pool,
        "stem": {"c_out": mc.stem.c_out, "k": mc.stem.kh,
                 "stride": mc.stem.stride},
        "stages": [[s.mid, s.out, s.blocks, s.stride] for s in mc.stages],
    }
    want = {k: cfg[k] for k in have}
    sp = mc.sparsity
    if have != want or (sp.nm.n, sp.nm.m) != (
            cfg["sparsity"]["n"], cfg["sparsity"]["m"]) or set(
            sp.targets) != set(cfg["sparsity"]["targets"]):
        raise SpecError(f"the program's {mc.name} is not the configuration "
                        f"file's: program {have}, {sp}; file {want}")
    mc = dataclasses.replace(mc, sparsity=dataclasses.replace(
        sp, use_kernel=bool(cfg["sparsity"]["kernels"])))
    return SparseCNN(mc)


def param_builder(model, cfg: dict, ref):
    """key -> the program's parameter tree, drawn from the seed. Tensors
    of one shape are drawn by one ``lax.map`` over their role ids, so the
    program holds one draw per shape and not one per tensor."""
    from repro.core.nmweight import NMWeight

    def is_nm(x):
        return isinstance(x, NMWeight)

    shapes = jax.eval_shape(lambda k: model.init(k, param_dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes,
                                                           is_leaf=is_nm)
    specs = ref.tensors(cfg)
    roles, groups = [], {}
    for path, leaf in leaves:
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        role = names[1] if names[0] == "convs" else names[0]
        dtype = (leaf.vals if is_nm(leaf) else leaf).dtype
        roles.append(role)
        groups.setdefault((specs[role], dtype), []).append(role)

    def build(key):
        drawn = {}
        for (spec, dtype), members in groups.items():
            ids = jnp.asarray([weights.role_id(r) for r in members], jnp.uint32)
            stack = jax.lax.map(
                lambda rid, spec=spec, dtype=dtype: ref.draw(
                    cfg, weights.role_key(key, rid), spec, dtype), ids)
            for i, r in enumerate(members):
                drawn[r] = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
        out = []
        for role, (_, leaf) in zip(roles, leaves):
            val = drawn[role]
            want = leaf.vals if is_nm(leaf) else leaf
            got = val[0] if is_nm(leaf) else val
            if got.shape != want.shape or got.dtype != want.dtype:
                raise SpecError(f"{role}: drew {got.shape} {got.dtype}, the "
                                f"program holds {want.shape} {want.dtype}")
            out.append(dataclasses.replace(leaf, vals=val[0], idx=val[1])
                       if is_nm(leaf) else val)
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def run(args: RunArgs) -> Outcome:
    cell, cfg, t = args.cell, args.cell.config, args.cell.traffic
    if t["kind"] != "offline_batches":
        raise SpecError(f"{cell.name}: traffic kind {t['kind']!r} is not "
                        "offline_batches")
    ref = cell.reference()
    setup = {"import_s": time.perf_counter() - args.t_process0}

    t0 = time.perf_counter()  # weights_s: compile or cache load, then draw
    model = program_model(cfg)
    key = weights.seed_key(args.seed)
    params = jax.jit(param_builder(model, cfg, ref))(key)
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()  # inputs_s: the images, likewise
    img_key = jax.random.fold_in(key, 0x1A9E)
    hw, nb, bs = cfg["input_hw"], t["batches"], t["batch"]
    images = jax.jit(lambda k: ref.images(k, nb, bs, hw))(img_key)
    images = [images[i] for i in range(nb)]  # no slicing inside the window
    jax.block_until_ready(images)
    setup["inputs_s"] = time.perf_counter() - t0

    fwd = jax.jit(lambda p, x: model.apply(p, x))
    warm = []  # each warm-up step's seconds: compile or cache load, work
    for i in range(t["warmup_steps"]):
        t0 = time.perf_counter()
        fwd(params, images[i % nb]).block_until_ready()
        warm.append(time.perf_counter() - t0)
    setup["warmup_s"] = sum(warm)
    print("warmup_step_s: " + " ".join(f"{x:.3f}" for x in warm), flush=True)
    compiled = fwd._cache_size()

    capture = devtrace.Capture(args.trace, f"{args.work_dir}/trace")
    outs = []
    with gcwatch.Frozen() as gcw:
        capture.start()
        setup_s = time.perf_counter() - args.t_process0
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            t_start = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.step"):
                    outs.append(fwd(params, images[len(outs) % nb]))
                if len(outs) > IN_FLIGHT:
                    outs[-1 - IN_FLIGHT].block_until_ready()
                    if time.perf_counter() - t_start >= args.seconds:
                        break
            jax.block_until_ready(outs)
            t_end = time.perf_counter()
        capture.stop()
    if fwd._cache_size() != compiled:
        raise RuntimeError("the step compiled inside the window")
    mem = device.memory_peak_bytes(jax, cell.chips)
    w = win.Window(t_start, t_end, len(outs), t["warmup_steps"])
    n_img = len(outs) * bs
    convs = ref.convs(cfg)
    counts = {"steps": w.steps, "images": n_img, "batch": bs}
    record = Record(
        cell=cell, peaks=args.peaks, window=w, counts=counts,
        work={"kernel_gemms": work.cnn_conv_gemms(cfg, convs, bs, True)
              * w.steps,
              "window_flops": work.cnn_image_flops(cfg, convs) * n_img},
        trace=capture.reduce(cell.chips))
    e2e = {"setup_s": setup_s, "images_per_s": n_img / w.seconds}
    print(f"window: {w.steps} steps of {bs} images from step "
          f"{w.warmup_steps}, {w.seconds:.3f} s", flush=True)
    print(gcw.summary(), flush=True)

    # --- the check, after the program's state is freed -----------------
    logits = np.stack([np.asarray(o, np.float32) for o in outs])
    del outs, params
    gc.collect()
    t0 = time.perf_counter()
    used = sorted({i % nb for i in range(w.steps)})
    errs, ctrl = [], []
    for b in used:
        r = np.asarray(ref.logits(cfg, key, images[b]), np.float32)
        errs.append(compare.max_rel_err(logits[b::nb], r[None]))
        if args.control:
            c = np.asarray(ref.logits(cfg, key, images[b], fp8=True))
            ctrl.append(compare.max_rel_err(c, r))
    compared = {"max_rel_err": max(errs)}
    control = {"max_rel_err": max(ctrl)} if ctrl else {}
    print(f"check: {n_img} images of {len(used)} batches against the "
          f"reference in {time.perf_counter() - t0:.1f} s", flush=True)
    return Outcome(end_to_end=e2e, setup=setup, compared=compared,
                   attempted=n_img, failed=0, memory_peak_bytes=mem,
                   record=record, control=control)
