import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell compiles.

For each cell this driver lowers + compiles the appropriate step —
``train_step`` (grad-accum + AdamW), ``serve_prefill`` or ``serve_decode``
— against ShapeDtypeStruct inputs on the production mesh (16x16 single-pod
and 2x16x16 multi-pod), prints ``memory_analysis()`` / ``cost_analysis()``,
runs the trip-count-aware HLO cost walker (hlo_cost.py) for the roofline
terms, and writes one JSON per cell under --out (resumable: existing cells
are skipped unless --force).

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k --mesh both --out results/dryrun

The paper's own workload is the additional arch id ``paper-tmfg``: the
column-sharded LAZY-TMFG construction + hub-APSP pipeline lowered on the
same meshes (core/distributed.py).
"""

import argparse
import json
import math
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, RunConfig, get_config, shapes_for
from repro.configs.shapes import SHAPES
from repro.dist import hints as hints_mod
from repro.dist import sharding as sh
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model, input_specs
from repro.train import optimizer
from repro.train.train_step import make_train_step

HW = dict(peak_flops_bf16=197e12, hbm_bw=819e9, link_bw=50e9)

# per-shape execution knobs (microbatching keeps the logits buffer in HBM;
# chunk sizes bound the attention working set)
SHAPE_KNOBS = {
    "train_4k": dict(microbatches=8, q_chunk=512, kv_chunk=1024),
    "prefill_32k": dict(microbatches=1, q_chunk=1024, kv_chunk=2048),
    "decode_32k": dict(),
    "long_500k": dict(),
}


def dp_axes(mesh):
    return sh.data_axes(mesh)


def _state_sharding_tree(state_sds, mesh, batch: int):
    """Generic decode-state sharding: batch dims over (pod,data); the
    longest remaining dim >= 4096 (sequence) over model (SP)."""
    axes = dp_axes(mesh)
    dp_total = sh.axis_size(mesh, axes)
    model = mesh.shape.get("model", 1)

    def leaf(x):
        shape = x.shape
        spec = [None] * len(shape)
        used_dp = False
        for i, s in enumerate(shape):
            if (not used_dp and s == batch and batch > 1
                    and batch % dp_total == 0):
                spec[i] = axes if len(axes) > 1 else axes[0]
                used_dp = True
                break
        # sequence dim: largest dim >= 4096 divisible by model
        cand = [(s, i) for i, s in enumerate(shape)
                if spec[i] is None and s >= 4096 and s % model == 0]
        if cand:
            _, i = max(cand)
            spec[i] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf, state_sds)


def _fits(mem) -> bool:
    if mem is None:
        return True
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    return total < 16e9  # v5e HBM


def _mem_dict(mem):
    if mem is None:
        return {}
    return dict(arg_bytes=mem.argument_size_in_bytes,
                out_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes)


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS for the whole step (all chips)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def roofline(totals: hlo_cost.CostTotals, n_dev: int, cfg, shape) -> dict:
    t_compute = totals.flops / HW["peak_flops_bf16"]
    t_memory = totals.hbm_bytes / HW["hbm_bw"]
    t_coll = totals.collective_wire_bytes / HW["link_bw"]
    dominant = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape) / n_dev if cfg is not None else 0.0
    return dict(
        t_compute_s=t_compute, t_memory_s=t_memory, t_collective_s=t_coll,
        dominant=dominant,
        hlo_flops_per_dev=totals.flops,
        hbm_bytes_per_dev=totals.hbm_bytes,
        wire_bytes_per_dev=totals.collective_wire_bytes,
        collective_counts=dict(totals.collective_counts),
        model_flops_per_dev=mf,
        useful_flops_ratio=(mf / totals.flops) if totals.flops else 0.0,
    )


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------

def build_train(cfg, shape, mesh, knobs, variant: str = "baseline"):
    """variant="opt" applies §Perf iteration 1: one-hot embedding +
    activation/logits/EP layout pins (kills the SPMD involuntary
    full-rematerialization cascade).  "opt-mb2" additionally drops grad
    accumulation from 8 to 2 microbatches (iteration 2: 4x fewer FSDP
    weight re-gathers; logits buffer stays in budget for vocab<=64k)."""
    model = build_model(cfg)
    mb = knobs.get("microbatches", 1)
    if "-mb2" in variant:
        mb = 2
    run_cfg = RunConfig(microbatches=mb)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_sds = jax.eval_shape(optimizer.init, params_sds)
    batch_sds = input_specs(cfg, shape, kind="train")

    embed_mode = "dmodel" if variant.startswith("opt") else "2d"
    if variant == "opt-vdata":
        embed_mode = "vdata"
    weights_mode = "tp_only" if variant.endswith("zero1") else "2d"
    param_sh = sh.param_shardings(params_sds, mesh, embed_mode=embed_mode,
                                  weights_mode=weights_mode)
    # optimizer state keeps full 2-D sharding regardless (ZeRO-1 split)
    opt_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        sh.param_specs(opt_sds, mesh, embed_mode=embed_mode))
    batch_sh = sh.batch_shardings(mesh, batch_sds)

    lk = dict(q_chunk=knobs.get("q_chunk", 512),
              kv_chunk=knobs.get("kv_chunk", 1024))
    if cfg.family in ("ssm",):
        lk = {}
    step = make_train_step(model, run_cfg, loss_kwargs=lk)
    if variant.startswith("opt"):
        axes = dp_axes(mesh)
        logits_hint = NamedSharding(mesh, P(axes, None, "model"))
        act_hint = None if variant == "opt-noact" else             NamedSharding(mesh, P(axes, None, None))
        inner_step = step

        def step_opt(params, opt_state, batch):
            with hints_mod.hints(logits=logits_hint, activations=act_hint,
                                 onehot_embed=True):
                return inner_step(params, opt_state, batch)

        step = step_opt
    jf = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                 out_shardings=(param_sh, opt_sh, None))
    return jf, (params_sds, opt_sds, batch_sds)


def build_prefill(cfg, shape, mesh, knobs, variant: str = "baseline"):
    model = build_model(cfg)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_sds = input_specs(cfg, shape, kind="prefill")
    embed_mode = "dmodel" if variant.startswith("opt") else "2d"
    param_sh = sh.param_shardings(params_sds, mesh, embed_mode=embed_mode)
    batch_sh = sh.batch_shardings(mesh, batch_sds)
    axes = dp_axes(mesh)
    kv_hint = NamedSharding(mesh, P(axes, "model", None, None))
    extra = {}
    if variant.startswith("opt"):
        extra = dict(
            moe_expert=NamedSharding(mesh, P("model", None, None)),
            activations=NamedSharding(mesh, P(axes, None, None)),
            onehot_embed=True,
        )

    qc = knobs.get("q_chunk", 1024)
    kc = knobs.get("kv_chunk", 2048)

    def serve_prefill(params, batch):
        with hints_mod.hints(kv_cache=kv_hint, **extra):
            if cfg.is_encdec:
                return model.prefill(params, batch["tokens"],
                                     batch["frontend"],
                                     max_len=shape.seq_len,
                                     q_chunk=qc, kv_chunk=kc)
            if cfg.family == "ssm":
                return model.prefill(params, batch["tokens"],
                                     max_len=shape.seq_len)
            return model.prefill(params, batch["tokens"],
                                 batch.get("frontend"),
                                 max_len=shape.seq_len,
                                 q_chunk=qc, kv_chunk=kc)

    jf = jax.jit(serve_prefill, in_shardings=(param_sh, batch_sh))
    return jf, (params_sds, batch_sds)


def build_decode(cfg, shape, mesh, knobs, variant: str = "baseline"):
    """variant="opt": int8 KV cache (halves the decode memory term —
    §Perf decode hillclimb; dense/moe/vlm archs only)."""
    kv_quant = variant.startswith("opt") and cfg.family in ("dense", "moe",
                                                            "vlm")
    model = build_model(cfg, kv_quant=kv_quant)
    B = shape.global_batch
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state_sds = jax.eval_shape(
        lambda: model.decode_state(B, shape.seq_len))
    param_sh = sh.param_shardings(params_sds, mesh)
    state_sh = _state_sharding_tree(state_sds, mesh, B)
    axes = dp_axes(mesh)
    dp_total = sh.axis_size(mesh, axes)
    tok_sh = NamedSharding(
        mesh, P(axes) if B % dp_total == 0 and B > 1 else P())

    def serve_decode(params, state, token, pos):
        return model.decode_step(params, state, token, pos)

    jf = jax.jit(serve_decode,
                 in_shardings=(param_sh, state_sh, tok_sh, None),
                 out_shardings=(None, state_sh))
    token_sds = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    return jf, (params_sds, state_sds, token_sds, pos_sds)


def build_tmfg(mesh, n=19456, L=64, collectives="batched"):
    """The paper's pipeline on the production mesh (arch id paper-tmfg).

    Two shapes for the §Perf A/B: "cluster" (batched per-step collectives,
    the optimized path) and "cluster-naive" (per-element baseline)."""
    from repro.core import distributed as DD

    axes = dp_axes(mesh)
    axis = axes if len(axes) > 1 else axes[0]

    def cluster_step(X):
        S = DD.pearson_sharded(X, mesh, axis)
        tm = DD.build_tmfg_sharded(S, mesh, axis=axis,
                                   collectives=collectives)
        return tm.edge_sum, tm.pops

    X_sds = jax.ShapeDtypeStruct((n, L), jnp.float32)
    jf = jax.jit(cluster_step,
                 in_shardings=(NamedSharding(mesh, P(axis, None)),))
    return jf, (X_sds,)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, variant: str = "baseline") -> dict:
    tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
    if variant != "baseline":
        tag += f"__{variant}"
    out_path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    os.makedirs(out_dir, exist_ok=True)
    rec = dict(arch=arch, shape=shape_name,
               mesh="2x16x16" if multi_pod else "16x16", ok=False)
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = int(np.prod(list(mesh.shape.values())))
        if arch == "paper-tmfg":
            coll = "per-element" if "naive" in shape_name else "batched"
            jf, sds = build_tmfg(mesh, collectives=coll)
            cfg, shape = None, None
        else:
            cfg = get_config(arch)
            shape = shapes_for(cfg).get(shape_name)
            assert shape is not None, \
                f"{shape_name} not applicable to {arch} (see DESIGN.md §5)"
            knobs = SHAPE_KNOBS.get(shape_name, {})
            if shape.kind == "train":
                jf, sds = build_train(cfg, shape, mesh, knobs, variant)
            elif shape.kind == "prefill":
                jf, sds = build_prefill(cfg, shape, mesh, knobs, variant)
            else:
                jf, sds = build_decode(cfg, shape, mesh, knobs, variant)

        lowered = jf.lower(*sds)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        print(f"[{tag}] memory_analysis: {mem}")
        ca = compiled.cost_analysis()
        print(f"[{tag}] cost_analysis flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}")
        hlo_text = compiled.as_text()
        import gzip
        with gzip.open(os.path.join(out_dir, tag + ".hlo.gz"), "wt") as g:
            g.write(hlo_text)
        totals = hlo_cost.analyze(hlo_text)
        rec.update(
            ok=True, lower_s=t_lower, compile_s=t_compile,
            memory=_mem_dict(mem), fits_hbm=_fits(mem),
            xla_cost=dict(flops=ca.get("flops"),
                          bytes=ca.get("bytes accessed")),
            roofline=roofline(totals, n_dev, cfg, shape),
            n_devices=n_dev,
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[{tag}] FAILED: {rec['error']}")
    rec["wall_s"] = time.time() - t0

    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK" if rec["ok"] else "FAIL"
    print(f"[{tag}] {status} in {rec['wall_s']:.1f}s")
    return rec


def cells(arch_filter=None, shape_filter=None, mesh_filter="both"):
    out = []
    archs = [arch_filter] if arch_filter and arch_filter != "all" \
        else ARCH_IDS + ["paper-tmfg"]
    for arch in archs:
        if arch == "paper-tmfg":
            shapes = ["cluster", "cluster-naive"]
        else:
            shapes = list(shapes_for(get_config(arch)))
        if shape_filter and shape_filter != "all":
            shapes = [s for s in shapes if s == shape_filter]
        for s in shapes:
            if mesh_filter in ("single", "both"):
                out.append((arch, s, False))
            if mesh_filter in ("multi", "both"):
                out.append((arch, s, True))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt", "opt-noact", "opt-vdata",
                             "opt-mb2", "opt-zero1", "opt-mb2-zero1"])
    args = ap.parse_args()

    todo = cells(args.arch, args.shape, args.mesh)
    print(f"dry-run: {len(todo)} cells")
    n_ok = 0
    for arch, shape, multi in todo:
        rec = run_cell(arch, shape, multi, args.out, force=args.force,
                       variant=args.variant)
        n_ok += bool(rec.get("ok"))
    print(f"dry-run complete: {n_ok}/{len(todo)} cells OK")
    if n_ok < len(todo):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
