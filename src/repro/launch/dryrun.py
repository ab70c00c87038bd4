import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the device
# count on first init). Everything below is ordinary code.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis import roofline as rl                    # noqa: E402
from repro.configs import (REGISTRY, SHAPES, TrainConfig,    # noqa: E402
                           applicable_shapes, get_config)
from repro.launch import sharding as sh                      # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402
from repro.models import build_model                         # noqa: E402
from repro.train import optimizer as opt_lib                 # noqa: E402
from repro.train.trainer import TrainState, make_train_step  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "dryrun_results")


def _sds_with_shardings(tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def _params_specs(model, mesh, mode):
    pshape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return _sds_with_shardings(pshape, sh.param_shardings(pshape, mesh, mode))


def analysis_config(cfg, shape, depth_units: int):
    """Variant used ONLY for flop/byte/collective accounting.

    XLA's HloCostAnalysis counts while/scan bodies ONCE (verified by
    calibration: an 8-step scan of matmuls reports 1 step's flops), so the
    production scan-over-layers program under-reports by ~L. We compile the
    same cell at depth 1 and depth 2 with single-chunk attention (q/kv
    chunks = seq, so no inner scan remains) and extrapolate linearly:
        f(L) = f(1) + (L - 1) * (f(2) - f(1)).
    Exact because every layer-scan body is shape-identical.
    """
    big = max(shape.seq_len, 1)
    kw = dict(q_chunk=big, kv_chunk=big, scan_unroll=True)
    if cfg.family == "hybrid":
        kw["n_layers"] = cfg.shared_attn_every * depth_units
    else:
        kw["n_layers"] = depth_units
        if cfg.encoder_layers:
            kw["encoder_layers"] = depth_units
    return cfg.scaled(**kw)


def depth_units_of(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.shared_attn_every
    return cfg.n_layers


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg_override=None, tcfg=None, param_mode=None,
               donate: bool = False):
    """Build (lowered, meta) for one (arch x shape x mesh) cell.

    Keyword knobs drive §Perf hillclimb variants:
      tcfg        — e.g. TrainConfig(microbatch=k) gradient accumulation
      param_mode  — "serve" in a train cell = TP-only params (no FSDP)
      donate      — alias state (train) / KV cache (decode) in-place
    """
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)

    with jax.set_mesh(mesh):
        batch_shape = model.make_input_specs(shape)
        batch = _sds_with_shardings(batch_shape,
                                    sh.batch_shardings(batch_shape, mesh))
        if shape.kind == "train":
            mode = param_mode or "train"
            params = _params_specs(model, mesh, mode)
            opt_shape = jax.eval_shape(opt_lib.init_opt_state, params)
            opt = _sds_with_shardings(
                opt_shape,
                jax.tree_util.tree_map_with_path(
                    lambda p, l: jax.sharding.NamedSharding(
                        mesh, sh.param_spec(p, l.shape, mesh, mode)),
                    opt_shape))
            state = TrainState(params=params, opt=opt)
            step_fn = make_train_step(model, tcfg or TrainConfig())
            lowered = jax.jit(
                step_fn, donate_argnums=(0,) if donate else ()).lower(
                    state, batch)
        elif shape.kind == "prefill":
            params = _params_specs(model, mesh, param_mode or "serve")
            lowered = jax.jit(model.prefill).lower(params, batch)
        else:  # decode
            params = _params_specs(model, mesh, param_mode or "serve")
            # per-device batch over `data`; seq dim of the cache over `model`
            cache_shape = model.init_cache_specs(shape.global_batch,
                                                 shape.seq_len)
            cache = _sds_with_shardings(
                cache_shape, sh.cache_shardings(cache_shape, mesh))
            b_ax = "data" if shape.global_batch % mesh.shape["data"] == 0 \
                else None
            tokens = jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32,
                sharding=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(b_ax)))
            lowered = jax.jit(
                model.decode_step,
                donate_argnums=(1,) if donate else ()).lower(
                    params, cache, tokens)
    return lowered, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR, save_hlo: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok"}
    cfg = get_config(arch)
    skip = dict(applicable_shapes(cfg)).get(SHAPES[shape_name].name)
    for s, reason in applicable_shapes(cfg):
        if s.name == shape_name and reason is not None:
            rec.update(status="skipped", reason=reason)
            _write(rec, out_dir)
            return rec
    t0 = time.time()
    try:
        lowered, mesh, cfg, shape = lower_cell(arch, shape_name, multi_pod)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        try:
            mem = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:  # CPU backend may not support it
            rec["memory_analysis"] = {"error": str(e)}
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            rec["cost_analysis"] = {k: float(v) for k, v in cost.items()
                                    if isinstance(v, (int, float))}
        except Exception as e:
            rec["cost_analysis"] = {"error": str(e)}

        hlo = compiled.as_text()
        coll = rl.parse_collectives(hlo)
        rec["collectives_raw"] = coll.to_json()
        if save_hlo:
            hpath = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}.hlo")
            with open(hpath, "w") as f:
                f.write(hlo)
        del compiled, lowered

        # --- accounting compiles (see analysis_config docstring): depth 1 &
        # 2 with single-chunk attention, then linear extrapolation in depth.
        probes = {}
        for u in (1, 2):
            lw, *_ = lower_cell(arch, shape_name, multi_pod,
                                cfg_override=analysis_config(cfg, shape, u))
            cm = lw.compile()
            ca = cm.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            pc = rl.parse_collectives(cm.as_text())
            probes[u] = (float(ca.get("flops", 0.0)),
                         float(ca.get("bytes accessed", 0.0)),
                         pc.moved_bytes, dict(pc.op_bytes), pc.n_ops)
            del cm, lw
        units = depth_units_of(cfg)

        def extrap(i):
            return probes[1][i] + (units - 1) * (probes[2][i] - probes[1][i])

        flops, bytes_acc, coll_moved = extrap(0), extrap(1), extrap(2)
        op_bytes = {
            k: probes[1][3].get(k, 0.0) + (units - 1)
            * (probes[2][3].get(k, 0.0) - probes[1][3].get(k, 0.0))
            for k in set(probes[1][3]) | set(probes[2][3])}
        n_ops = probes[1][4] + (units - 1) * (probes[2][4] - probes[1][4])
        coll_x = rl.CollectiveStats(op_bytes=op_bytes,
                                    moved_bytes=coll_moved, n_ops=n_ops)
        rec["collectives"] = coll_x.to_json()
        rec["probe_depths"] = {str(u): probes[u][:3] for u in probes}
        # roofline table is single-pod (harness contract); the multi-pod
        # pass proves the pod axis shards. ICI bandwidth for the link term.
        n_dev = 512 if multi_pod else 256
        mf = rl.model_flops_for(cfg, shape, rl.active_params(cfg))
        roof = rl.compute_roofline(flops, bytes_acc, coll_x, n_dev, mf,
                                   link_bw=rl.ICI_BW)
        rec["roofline"] = roof.to_json()
    except Exception:
        rec["status"] = "error"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    _write(rec, out_dir)
    return rec


def _write(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    archs = sorted(REGISTRY) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(
                    args.out, f"{arch}_{shape}_{mesh_name}.json")
                if args.skip_done and os.path.exists(path):
                    with open(path) as f:
                        old = json.load(f)
                    if old.get("status") in ("ok", "skipped"):
                        print(f"[dryrun] {arch} {shape} {mesh_name}: cached",
                              flush=True)
                        continue
                rec = run_cell(arch, shape, mp, args.out, args.save_hlo)
                msg = rec["status"]
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    msg += (f" compile={rec['compile_s']}s"
                            f" bottleneck={r['bottleneck']}"
                            f" terms=({r['compute_s']:.2e},"
                            f"{r['memory_s']:.2e},{r['collective_s']:.2e})s")
                elif rec["status"] == "skipped":
                    msg += f" ({rec['reason']})"
                print(f"[dryrun] {arch} {shape} {mesh_name}: {msg}",
                      flush=True)


if __name__ == "__main__":
    main()
