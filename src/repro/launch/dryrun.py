"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without real hardware:
  * builds the production mesh (16x16 single pod / 2x16x16 multi-pod) on 512
    placeholder host devices (`force_host_devices`, called by `main` before
    the first device query, so importing this module changes nothing),
  * lowers train_step / prefill_step / serve_step against ShapeDtypeStruct
    inputs (zero allocation) with the full DP/FSDP/TP/EP sharding rules,
  * compiles, prints memory_analysis() (proves the per-device footprint) and
    cost_analysis(), and extracts trip-count-corrected matmul FLOPs +
    per-kind collective bytes from the optimized HLO (hlo_analysis.py),
  * writes one JSON per cell under --out for §Dry-run/§Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.attention import AttentionSpec
from repro.configs import SHAPES, all_arch_ids, get_config
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_prefill_step, make_serve_step,
                                make_train_step, pick_optimizer)
from repro.models import (decode_state_specs, init_model, input_specs)
from repro.sharding import (batch_spec, decode_state_shardings,
                            param_shardings)

# v5e constants for the roofline terms (per task spec)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_REMAT_MSG = "Involuntary full rematerialization"


@contextlib.contextmanager
def _capture_xla_warnings(out: dict):
    """Capture fd-2 around lower/compile: the SPMD partitioner logs
    "Involuntary full rematerialization" from C++ (invisible to Python
    logging). Records count + first lines in `out` and re-emits everything
    to the real stderr, so the sharding-health signal becomes a machine-
    checkable part of the dry-run result JSON (--assert-no-remat gates on
    it)."""
    sys.stderr.flush()
    try:
        saved = os.dup(2)
    except OSError:
        yield
        return
    with tempfile.TemporaryFile(mode="w+b") as tmp:
        os.dup2(tmp.fileno(), 2)
        try:
            yield
        finally:
            # re-emit + record in the finally so a raising cell still
            # surfaces XLA's stderr (compile errors!) and its remat count
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
            tmp.seek(0)
            text = tmp.read().decode("utf-8", "replace")
            if text:
                sys.stderr.write(text)
                sys.stderr.flush()
            remat = [ln for ln in text.splitlines() if _REMAT_MSG in ln]
            out["xla_remat"] = {
                "count": len(remat),
                "lines": [ln[:400] for ln in remat[:8]],
            }


@contextlib.contextmanager
def _kernel_cell_env(cfg):
    """kernel-impl cells must exercise the kernel protocol, not the
    platform fallback: REPRO_DECODE_KERNEL=1 forces the (shard_map-wrapped
    under the mesh) Pallas decode path, in interpret mode on this CPU host
    — the compiled HLO still proves the partitioning. An explicit
    REPRO_DECODE_KERNEL in the environment wins."""
    prev = os.environ.get("REPRO_DECODE_KERNEL")
    if prev is None and cfg.attn.family in ("fastmax", "hybrid") \
            and cfg.attn.impl == "kernel":
        os.environ["REPRO_DECODE_KERNEL"] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_DECODE_KERNEL", None)


def _tree_size_bytes(tree) -> int:
    return sum(int(jnp.prod(jnp.asarray(x.shape)) * x.dtype.itemsize)
               if hasattr(x, "shape") else 0
               for x in jax.tree.leaves(tree))


def _opt_shardings(opt_shapes, param_sh, mesh):
    """Optimizer state shardings: moments/master like params; step replicated.
    (Lion m / AdamW m,v,master all have param shapes.)"""
    rep = NamedSharding(mesh, P())

    def like_params(sub):
        if sub is None:
            return None
        return jax.tree.map(lambda _, s: s, sub, param_sh)

    from repro.optim.optimizers import OptState
    return OptState(
        step=rep,
        m=like_params(opt_shapes.m),
        v=like_params(opt_shapes.v),
        master=like_params(opt_shapes.master),
    )


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             attn: AttentionSpec | str | None = None, donate: bool = True,
             extra_cfg: dict | None = None, cp: int = 1) -> dict:
    t0 = time.time()
    shape = SHAPES[shape_name]
    overrides = dict(extra_cfg or {})
    if attn:
        overrides["attn"] = (AttentionSpec.parse(attn)
                             if isinstance(attn, str) else attn)
    cfg = get_config(arch, **overrides)

    if shape_name == "long_500k" and cfg.attn.family == "softmax" \
            and cfg.family not in ("ssm", "hybrid"):
        return {"arch": arch, "shape": shape_name, "skipped":
                "long_500k needs sub-quadratic attention; softmax baseline "
                "is pure full attention (DESIGN.md §Arch-applicability)"}

    # record this cell's attention routing decisions (the _log_once lines:
    # backend reroutes, kernel shard_map plans, jnp fallbacks) so the
    # result JSON is machine-checkable (--assert-kernel-route), and the
    # autotune lookups so the result also pins WHICH kernel schedule each
    # launch traced with (cache hit/miss next to attn_routing)
    from repro.attention.registry import _LOGGED
    from repro.kernels import autotune
    _LOGGED.clear()
    autotune.clear_lookups()

    mesh = make_production_mesh(multi_pod=multi_pod, cp=cp)
    n_chips = mesh.devices.size
    if cp > 1 and shape.seq_len % cp:
        raise ValueError(f"--cp {cp} must divide seq_len={shape.seq_len}")
    key = jax.random.PRNGKey(0)
    params_shapes, axes = init_model(key, cfg, abstract=True)
    n_params = sum(int(jnp.prod(jnp.asarray(x.shape)))
                   for x in jax.tree.leaves(params_shapes))

    xla_diag: dict = {}
    with _capture_xla_warnings(xla_diag), _kernel_cell_env(cfg), mesh:
        param_sh = param_shardings(axes, params_shapes, mesh)

        if shape.kind == "train":
            opt_name, optimizer = pick_optimizer(cfg, n_params)
            opt_init, _ = optimizer
            opt_shapes = jax.eval_shape(opt_init, params_shapes)
            opt_sh = _opt_shardings(opt_shapes, param_sh, mesh)
            bspec = batch_spec(mesh, batch_size=shape.global_batch)
            batch_shapes = input_specs(cfg, global_batch=shape.global_batch,
                                       seq_len=shape.seq_len, kind="train")
            batch_sh = jax.tree.map(
                lambda s: NamedSharding(
                    mesh, P(*(list(bspec) + [None] * (len(s.shape) - 1)))),
                batch_shapes)
            step = make_train_step(cfg, optimizer)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, None),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(params_shapes, opt_shapes, batch_shapes)
        elif shape.kind == "prefill":
            state_shapes = decode_state_specs(cfg, shape.global_batch,
                                              shape.seq_len)
            state_sh = decode_state_shardings(state_shapes, mesh,
                                              batch=shape.global_batch)
            bspec = batch_spec(mesh, batch_size=shape.global_batch)
            tok = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32)
            tok_sh = NamedSharding(mesh, P(*(list(bspec) + [None])))
            step = make_prefill_step(cfg)
            args = [params_shapes, state_shapes, tok]
            in_sh = [param_sh, state_sh, tok_sh]
            if cfg.encoder_layers > 0:
                enc = jax.ShapeDtypeStruct(
                    (shape.global_batch, cfg.encoder_seq, cfg.d_model),
                    cfg.adtype())
                args.append(enc)
                in_sh.append(NamedSharding(mesh,
                                           P(*(list(bspec) + [None, None]))))
            jitted = jax.jit(
                step, in_shardings=tuple(in_sh),
                out_shardings=(NamedSharding(mesh, P(*list(bspec))),
                               state_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(*args)
        else:  # decode
            state_shapes = decode_state_specs(cfg, shape.global_batch,
                                              shape.seq_len)
            state_sh = decode_state_shardings(state_shapes, mesh,
                                              batch=shape.global_batch)
            bspec = batch_spec(mesh, batch_size=shape.global_batch)
            tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            tok_sh = NamedSharding(
                mesh, P(*list(bspec)) if shape.global_batch > 1 else P(None))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            step = make_serve_step(cfg)
            args = [params_shapes, state_shapes, tok, pos]
            in_sh = [param_sh, state_sh, tok_sh, NamedSharding(mesh, P())]
            if cfg.encoder_layers > 0:
                enc = jax.ShapeDtypeStruct(
                    (shape.global_batch, cfg.encoder_seq, cfg.d_model),
                    cfg.adtype())
                args.append(enc)
                in_sh.append(NamedSharding(
                    mesh, P(*((list(bspec) if shape.global_batch > 1
                               else [None]) + [None, None]))))
            jitted = jax.jit(
                step, in_shardings=tuple(in_sh),
                out_shardings=(tok_sh, state_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(*args)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = analyze_hlo(compiled.as_text())

    # --- roofline terms (see EXPERIMENTS.md §Roofline) ---------------------
    # the compiled module is the PER-DEVICE program: flops/bytes are per chip
    flops_dev = hlo["matmul_flops"]
    coll = hlo["collective_bytes"]
    hbm = hlo["hbm_bytes"]
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = hbm / HBM_BW                           # per-chip stream time
    collective_s = coll / ICI_BW                      # per-chip link time

    # MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens (serve)
    flat = jax.tree_util.tree_flatten_with_path(params_shapes)[0]
    ax_flat = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    total_p = routed_p = embed_p = 0
    for (path, leaf), ax in zip(flat, ax_flat):
        npx = 1
        for d in leaf.shape:
            npx *= int(d)
        total_p += npx
        if "experts" in ax:
            routed_p += npx
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "embed":
            embed_p += npx
    active_p = total_p - (0 if cfg.n_experts == 0 else
                          routed_p * (1.0 - cfg.moe_top_k / cfg.n_experts))
    if not cfg.tie_embeddings:
        active_p -= embed_p
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    model_flops = (6.0 if shape.kind == "train" else 2.0) * active_p * tokens
    useful_ratio = model_flops / max(1.0, flops_dev * n_chips)

    cp_boundary = None
    if cp > 1 and shape.kind == "train":
        # modeled per-boundary collective bytes of the context-parallel
        # carry exchange, next to the ring-attention O(N·D) alternative —
        # the gate asserts the carry payload is independent of N
        from repro.kernels.sharded import cp_boundary_model
        cp_boundary = cp_boundary_model(
            n=shape.seq_len, b=shape.global_batch, hkv=cfg.n_kv_heads,
            d=cfg.head_dim, dv=cfg.head_dim, p=cfg.attn.p, cp=cp)

    out = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "cp": cp,
        "cp_boundary": cp_boundary,
        "xla_remat": xla_diag.get("xla_remat", {"count": 0, "lines": []}),
        "attn_routing": sorted(_LOGGED),
        "attn_schedule": autotune.snapshot_lookups(),
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "n_chips": int(n_chips),
        "attn_backend": cfg.attn.legacy_name,   # result-JSON back-compat key
        "attn_spec": str(cfg.attn),
        "n_params": int(n_params),
        "param_bytes_global": _tree_size_bytes(params_shapes),
        "memory_analysis": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "alias_size": getattr(mem, "alias_size_in_bytes", None),
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))
                          and k in ("flops", "bytes accessed")},
        "hlo": {k: float(v) for k, v in hlo.items()},
        "model_flops": model_flops,
        "active_params": float(active_p),
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "useful_flops_ratio": useful_ratio,
            "dominant": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
        },
        "compile_seconds": time.time() - t0,
    }
    return out


def force_host_devices(n: int = 512) -> None:
    """Give the CPU backend `n` placeholder devices for the production
    meshes. Takes effect only before JAX's first device query."""
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--attn", default=None,
                    help="attention operator (AttentionSpec.parse name, "
                         "e.g. softmax, fastmax2, fastmax2-kernel)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel degree: trade the 'model' mesh "
                         "axis for a 'seq' axis of this size (train cells; "
                         "fastmax routes shard_map[seq])")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--assert-no-remat", action="store_true",
                    help="fail a cell if the SPMD partitioner logged any "
                         "'Involuntary full rematerialization' (sharding-"
                         "annotation health gate; see ROADMAP serve-path "
                         "item)")
    ap.add_argument("--assert-kernel-route", action="store_true",
                    help="fail a cell if the decode protocol fell back to "
                         "the jnp moment step (a '-> jnp' routing line): "
                         "proves the shard_map-wrapped Pallas kernels are "
                         "the decode path at this mesh/shape")
    args = ap.parse_args()

    archs = all_arch_ids() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}" \
                    + (f"__{args.attn}" if args.attn else "") \
                    + (f"__cp{args.cp}" if args.cp > 1 else "")
                try:
                    res = run_cell(arch, shape, multi_pod=multi,
                                   attn=args.attn, cp=args.cp)
                    status = "SKIP" if "skipped" in res else "OK"
                    gate_errs = []
                    n_remat = res.get("xla_remat", {}).get("count", 0)
                    if args.assert_no_remat and n_remat:
                        gate_errs.append(
                            f"{n_remat} involuntary full "
                            f"rematerialization warning(s)")
                    routing = res.get("attn_routing", [])
                    # fallback lines: the decode protocol's "-> jnp" moment
                    # step AND the trainable path's "-> chunked scan"
                    # (feature-TP training must stay on the shard_map
                    # Pallas kernels); the benign "-> interpret mode"
                    # platform note is not a fallback
                    falls = [ln for ln in routing
                             if "-> jnp" in ln or "-> chunked scan" in ln]
                    routed = any("kernel shard_map[" in ln
                                 for ln in routing)
                    if args.assert_kernel_route and status == "OK":
                        # require the POSITIVE shard_map routing line too —
                        # an empty/disabled routing record must not pass
                        # the gate vacuously
                        if falls:
                            gate_errs.append("attention fell back off the "
                                             "kernels: " + falls[0])
                        elif not routed:
                            gate_errs.append(
                                "no shard_map kernel routing line recorded "
                                "(REPRO_DECODE_KERNEL disabled, or a "
                                "non-kernel cell?)")
                    if gate_errs:
                        status = "FAIL"
                        failures += 1
                        res["error"] = "; ".join(gate_errs)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    status = "FAIL"
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=2)
                if not args.quiet:
                    line = f"[{status}] {tag}"
                    if status == "OK":
                        r = res["roofline"]
                        line += (f"  compute={r['compute_s']:.3e}s "
                                 f"memory={r['memory_s']:.3e}s "
                                 f"collective={r['collective_s']:.3e}s "
                                 f"dominant={r['dominant']} "
                                 f"compile={res['compile_seconds']:.0f}s")
                        ma = res["memory_analysis"]
                        line += (f" argbytes/dev={ma['argument_size']} "
                                 f"temp/dev={ma['temp_size']}")
                    elif status == "FAIL":
                        line += "  " + res["error"][:160]
                    print(line, flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
