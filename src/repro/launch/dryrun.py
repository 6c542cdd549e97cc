import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("DRYRUN_DEVICES", "512")
    + " " + os.environ.get("XLA_FLAGS_EXTRA", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import (jax locks the device count
on first init).  For each cell this driver:

  1. builds the production mesh (16x16 single pod / 2x16x16 multi-pod);
  2. resolves the model's logical shard specs against it;
  3. ``jit(step).lower(**ShapeDtypeStructs).compile()`` — no allocation;
  4. records memory_analysis / cost_analysis / collective traffic and the
     three roofline terms into one JSON per cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single,multi --out benchmarks/results/dryrun
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

from ..configs import ARCHS, get_arch
from ..distributed.sharding import get_rules, named_sharding
from ..models import SHAPES, get_shape, shape_applicable
from ..models.config import ModelConfig, ShapeConfig
from .analysis import model_flops_for, param_counts, roofline_from_compiled
from .mesh import mesh_for_name
from .steps import (
    abstract_cache,
    abstract_params,
    abstract_train_state,
    input_spec_names,
    input_specs,
    make_decode_fn,
    make_prefill_step,
    make_train_step,
    train_state_specs,
)
from ..models import cache_specs as model_cache_specs
from ..models import param_specs as model_param_specs


def _resolve_tree(mesh, spec_tree, abstract_tree=None):
    """Logical specs -> NamedShardings, pruning axes that don't divide.

    Argument shardings (unlike in-function constraints) must divide the
    dimension exactly; dims like batch=1 or head counts not divisible by the
    TP degree fall back to replication on that dim.
    """
    rules = get_rules()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def leaf(spec, aval=None):
        pspec = rules.resolve(mesh.axis_names, *spec)
        if aval is not None:
            pruned = []
            for dim, ax in zip(aval.shape, tuple(pspec) + (None,) * (len(aval.shape) - len(pspec))):
                if ax is None:
                    pruned.append(None)
                    continue
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                total = 1
                for a in axes:
                    total *= sizes.get(a, 1)
                pruned.append(ax if dim % total == 0 else None)
            pspec = jax.sharding.PartitionSpec(*pruned)
        return NamedSharding(mesh, pspec)

    if abstract_tree is None:
        return jax.tree.map(leaf, spec_tree, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(
        lambda s, a: leaf(s, a), spec_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def run_cell(arch: str, shape_name: str, mesh_name: str, impl: str = "reference",
             moe_groups: int = 1, grad_accum: Optional[int] = None) -> Dict[str, Any]:
    import dataclasses

    cfg = get_arch(arch)
    if moe_groups > 1 and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch_groups=moe_groups)
        )
    if grad_accum is not None:
        cfg = dataclasses.replace(cfg, grad_accum=grad_accum)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": shape.mode, "status": "skipped", "reason": reason,
    }
    if not ok:
        return result

    mesh = mesh_for_name(mesh_name)
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if shape.mode == "train":
                step = make_train_step(cfg, impl=impl)
                state = abstract_train_state(cfg)
                state_sh = _resolve_tree(mesh, train_state_specs(cfg, tp), state)
                batch = input_specs(cfg, shape)
                batch_sh = _resolve_tree(mesh, input_spec_names(cfg, shape), batch)
                lowered = jax.jit(
                    step,
                    in_shardings=(state_sh, batch_sh),
                    donate_argnums=(0,),
                ).lower(state, batch)
            elif shape.mode == "prefill":
                step = make_prefill_step(cfg, impl=impl)
                params = abstract_params(cfg)
                params_sh = _resolve_tree(mesh, model_param_specs(cfg, tp), params)
                batch = input_specs(cfg, shape)
                batch_sh = _resolve_tree(mesh, input_spec_names(cfg, shape), batch)
                lowered = jax.jit(
                    step, in_shardings=(params_sh, batch_sh)
                ).lower(params, batch)
            else:  # decode
                step = make_decode_fn(cfg)
                params = abstract_params(cfg)
                params_sh = _resolve_tree(mesh, model_param_specs(cfg, tp), params)
                cache = abstract_cache(cfg, shape)
                cache_sh = _resolve_tree(mesh, model_cache_specs(cfg, tp), cache)
                tok = input_specs(cfg, shape)["token"]
                tok_sh = _resolve_tree(
                    mesh, {"token": ("batch", None)}, {"token": tok}
                )["token"]
                lowered = jax.jit(
                    step,
                    in_shardings=(params_sh, cache_sh, tok_sh),
                    donate_argnums=(1,),
                ).lower(params, cache, tok)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mf = model_flops_for(cfg, shape)
        terms = roofline_from_compiled(compiled, n_dev, mf)
        result.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_devices=n_dev,
            param_counts=param_counts(cfg),
            roofline=terms.as_dict(),
        )
        ms = terms.memory_stats
        if ms:
            result["bytes_per_device"] = ms.get("peak_hbm_bytes")
            result["fits_16gb_hbm"] = bool(ms.get("peak_hbm_bytes", 0) <= 16e9)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--impl", default="reference")
    ap.add_argument("--moe-groups", type=int, default=1)
    ap.add_argument("--grad-accum", type=int, default=0)
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = [s.name for s in SHAPES] if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    os.makedirs(args.out, exist_ok=True)

    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                tag = f"{arch}_{shape}_{mesh}".replace("/", "-")
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                res = run_cell(arch, shape, mesh, impl=args.impl,
                               moe_groups=args.moe_groups,
                               grad_accum=args.grad_accum or None)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                if res["status"] == "ok":
                    r = res["roofline"]
                    print(
                        f"[ok]   {tag}: compile={res['compile_s']}s "
                        f"dominant={r['dominant']} compute={r['compute_s']:.3e}s "
                        f"mem={r['memory_s']:.3e}s coll={r['collective_s']:.3e}s "
                        f"useful={r['useful_ratio']:.2f}"
                    )
                elif res["status"] == "skipped":
                    print(f"[skip] {tag}: {res['reason']}")
                else:
                    print(f"[ERR]  {tag}: {res['error']}")


if __name__ == "__main__":
    main()
