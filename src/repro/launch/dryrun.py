import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import: jax locks the device count on first init.

"""Multi-pod dry-run driver.

For every (architecture × input shape) cell, lower + compile the appropriate
step function on the production mesh (single-pod 16x16 = 256 chips, and
multi-pod 2x16x16 = 512 chips), print memory/cost analysis, and emit the
roofline terms as JSON for EXPERIMENTS.md.

    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_arch, list_archs
from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh, make_rules
from repro.launch import specs as S
from repro.models import transformer as T
from repro.roofline import analysis as R
from repro.train import step as TS


def arch_train_config(cfg: ArchConfig, overrides=None) -> TS.TrainConfig:
    """Per-arch defaults: microbatching + attention impl scale with size."""
    n = cfg.param_count
    micro = 8 if n > 100e9 else (4 if n > 10e9 else 1)
    kw = dict(
        microbatches=micro,
        accum_dtype="bfloat16" if n > 100e9 else "float32",
        attn_impl="dense",
        attn_chunk=1024,
    )
    if overrides:
        kw.update(overrides)
    return TS.TrainConfig(**kw)


def wants_fsdp(cfg: ArchConfig) -> bool:
    return cfg.param_count > 10e9


def _shard(mesh, spec_tree, shape_tree):
    return jax.tree.map(
        lambda _, s: NamedSharding(mesh, s), shape_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batch_axes_for(shape: ShapeConfig, mesh):
    """Drop batch axes that don't divide the global batch (e.g. long_500k
    with batch=1 stays unsharded)."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    keep = []
    b = shape.global_batch
    for a in axes:
        n = mesh.shape[a]
        if b % n == 0:
            keep.append(a)
            b //= n
    return tuple(keep)


def lower_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               overrides=None, verbose=True, compression=False,
               seq_shard=False, fsdp: str = "auto", pipeline=False):
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    use_fsdp = {"auto": wants_fsdp(cfg), "on": True, "off": False}[fsdp]
    rules = make_rules(mesh, fsdp=use_fsdp, seq=seq_shard)
    batch_axes = _batch_axes_for(shape, mesh)
    # kv/q replication (model_size-aware pspecs) pays off only without a
    # backward pass: in training, the gradient of a replicated wk/wv needs
    # an activation-sized model-axis all-reduce that outweighs the saved
    # score partial-sums (§Perf, measured on qwen3). Serving has no bwd.
    eff_model_size = 1   # kv-replication refuted for decode too (see §Perf)
    rules = T.ShardRules(batch=batch_axes,
                         model=rules.model, fsdp=rules.fsdp, seq=rules.seq,
                         moe_groups=_prod(mesh, batch_axes),
                         model_size=eff_model_size)
    dtype = jnp.bfloat16
    t0 = time.time()

    with jax.set_mesh(mesh):
        return _lower_cell_inner(cfg, shape, arch_name, shape_name, mesh,
                                 chips, rules, dtype, t0, overrides,
                                 verbose, compression, pipeline)


def _lower_cell_inner(cfg, shape, arch_name, shape_name, mesh, chips, rules,
                      dtype, t0, overrides, verbose, compression,
                      pipeline=False):
    if pipeline:
        assert shape.kind == "train" and "pod" in mesh.axis_names, \
            "--pipeline needs a train shape on the multi-pod mesh"
        lowered = _lower_pipeline(cfg, shape, mesh, rules, dtype, overrides)
    elif shape.kind == "train":
        tc = arch_train_config(cfg, overrides)
        if shape.global_batch % (max(1, _prod(mesh, rules.batch))
                                 * tc.microbatches):
            tc = TS.TrainConfig(**{**tc.__dict__, "microbatches": 1})
        pshapes, sshapes = _train_shapes(cfg, tc, dtype)
        pspec, sspec = TS.train_state_pspecs(cfg, tc, rules, pshapes)
        bspec = S.input_pspecs(cfg, rules)
        (inputs,) = S.input_specs(cfg, shape, dtype)
        if compression:
            tc = TS.TrainConfig(**{**tc.__dict__,
                                   "grad_compression": "int8_pod"})
            pshapes, sshapes = _train_shapes(cfg, tc, dtype)
            pspec, sspec = TS.train_state_pspecs(cfg, tc, rules, pshapes)
            step = TS.make_compressed_train_step(cfg, tc, rules, mesh)
        else:
            step = TS.make_train_step(cfg, tc, rules)
        fn = jax.jit(
            step,
            in_shardings=(_shard(mesh, pspec, pshapes),
                          _shard(mesh, sspec, sshapes),
                          _shard(mesh, bspec, inputs)),
            out_shardings=(_shard(mesh, pspec, pshapes),
                           _shard(mesh, sspec, sshapes), None))
        lowered = fn.lower(pshapes, sshapes, inputs)
    elif shape.kind == "prefill":
        (inputs,) = S.input_specs(cfg, shape, dtype)
        pshapes = T.param_shapes(cfg, dtype)
        pspec = T.param_pspecs(cfg, rules)
        bspec = S.input_pspecs(cfg, rules)
        bspec.pop("labels", None)
        impl = "chunked" if shape.seq_len > 8192 else "dense"

        def prefill(params, batch):
            logits, _ = T.forward(params, cfg, batch, impl=impl,
                                  chunk=1024, rules=rules, remat=False)
            return logits

        fn = jax.jit(prefill,
                     in_shardings=(_shard(mesh, pspec, pshapes),
                                   _shard(mesh, bspec, inputs)),
                     out_shardings=None)
        lowered = fn.lower(pshapes, inputs)
    else:  # decode
        inputs, cache = S.input_specs(cfg, shape, dtype)
        pshapes = T.param_shapes(cfg, dtype)
        pspec = T.param_pspecs(cfg, rules)
        cspec = T.cache_pspecs(cfg, rules)
        ispec = {k: P(*((rules.batch,) + (None,) * (v.ndim - 1)))
                 if k not in ("length", "positions")
                 else (P() if k == "length" else P(None, rules.batch, None))
                 for k, v in inputs.items()}

        def serve_step(params, cache, batch):
            return T.decode_step(params, cfg, cache, batch, rules=rules)

        fn = jax.jit(serve_step,
                     in_shardings=(_shard(mesh, pspec, pshapes),
                                   _shard(mesh, cspec, cache),
                                   _shard(mesh, ispec, inputs)),
                     out_shardings=(None, _shard(mesh, cspec, cache)))
        lowered = fn.lower(pshapes, cache, inputs)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = R.analyze_compiled(compiled, chips=chips)
    mem = R.parse_memory_analysis(compiled)
    roof = R.Roofline(
        arch=arch_name, shape=shape_name,
        mesh="x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        chips=chips, hlo_flops=cost["flops"], hlo_bytes=cost["bytes"],
        collective_bytes=cost["collective_bytes"],
        model_flops=R.model_flops(cfg, shape),
        per_device_hbm=(mem / chips if mem else None),
        dot_flops=cost["dot_flops"], coll_counts=cost["coll_counts"])
    if verbose:
        print(f"== {arch_name} x {shape_name} on {roof.mesh} "
              f"({chips} chips) ==")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"   memory_analysis: {compiled.memory_analysis()}")
        print(f"   hlo_flops={cost['flops']:.3e} "
              f"(dot {cost['dot_flops']:.3e}) bytes={cost['bytes']:.3e}")
        print(f"   collective_bytes={cost['collective_bytes']:.3e} "
              f"counts={cost['coll_counts']}")
        r = roof.row()
        print(f"   t_compute={r['t_compute_s']:.4f}s "
              f"t_memory={r['t_memory_s']:.4f}s "
              f"t_collective={r['t_collective_s']:.4f}s "
              f"-> bottleneck={r['bottleneck']}")
        print(f"   useful_flop_ratio={r['useful_flop_ratio']:.3f} "
              f"roofline_fraction={r['roofline_fraction']:.3f}")
    return roof


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _lower_pipeline(cfg, shape, mesh, rules, dtype, overrides):
    """GPipe over the 'pod' axis: blocks stage-sharded, TP inside stages."""
    from repro.train.pipeline import (PipelineConfig, init_pp_state,
                                      make_pp_train_step)
    tc = arch_train_config(cfg, overrides)
    pc = PipelineConfig(n_stages=mesh.shape["pod"],
                        microbatches=max(tc.microbatches, 4))
    # inner (per-stage) rules: data/model only
    inner = T.ShardRules(batch=tuple(a for a in rules.batch if a != "pod"),
                         model=rules.model, fsdp=rules.fsdp,
                         moe_groups=1)
    pshapes, sshapes = jax.eval_shape(
        lambda k: init_pp_state(k, cfg, tc, pc, dtype), jax.random.key(0))
    # shardings: blocks (S, L/S, ...) -> pod on dim0 + usual TP/FSDP inside
    base_pspec = T.param_pspecs(cfg, inner)

    def _shift(spec):
        return P(*(("pod",) + tuple(spec)))

    pspec = dict(base_pspec)
    pspec["blocks"] = jax.tree.map(_shift, base_pspec["blocks"])
    opt_like = sshapes["opt"]

    def _opt_spec(tree, under_blocks=False):
        if isinstance(tree, dict):
            return {k: _opt_spec(v, under_blocks or k == "blocks")
                    for k, v in tree.items()}
        return P("pod") if under_blocks else P()
    sspec = {"opt": _opt_spec(opt_like), "step": P()}
    (inputs,) = S.input_specs(cfg, shape, dtype)
    bspec = S.input_pspecs(cfg, inner)
    step = make_pp_train_step(cfg, tc, pc, inner, mesh)
    fn = jax.jit(step,
                 in_shardings=(_shard(mesh, pspec, pshapes),
                               _shard(mesh, sspec, sshapes),
                               _shard(mesh, bspec, inputs)),
                 out_shardings=(_shard(mesh, pspec, pshapes),
                                _shard(mesh, sspec, sshapes), None))
    return fn.lower(pshapes, sshapes, inputs)


def _train_shapes(cfg, tc, dtype):
    return TS.train_state_shapes(cfg, tc, dtype)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--compression", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--pipeline", action="store_true",
                    help="GPipe over the pod axis (multi-pod train only)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--attn-impl", default=None)
    args = ap.parse_args(argv)

    overrides = {}
    if args.micro:
        overrides["microbatches"] = args.micro
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl

    cells = []
    if args.all:
        for a in list_archs():
            cfg = get_arch(a)
            for sname in SHAPES:
                if sname in cfg.skip_shapes:
                    print(f"-- skip {a} x {sname} "
                          f"(sub-quadratic requirement; see DESIGN.md)")
                    continue
                cells.append((a, sname))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    rows, failures = [], []
    for arch, shape in cells:
        for mp in meshes:
            try:
                roof = lower_cell(arch, shape, multi_pod=mp,
                                  overrides=overrides or None,
                                  compression=args.compression,
                                  seq_shard=args.seq_shard,
                                  fsdp=args.fsdp,
                                  pipeline=args.pipeline)
                rows.append(roof.row())
            except Exception as e:  # noqa: BLE001 — report all failures
                failures.append((arch, shape, mp, repr(e)[:500]))
                print(f"!! FAIL {arch} x {shape} multi_pod={mp}: "
                      f"{repr(e)[:300]}", file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "failures": failures}, f, indent=1)
    print(f"\n{len(rows)} cells OK, {len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
