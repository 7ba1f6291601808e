import os
# 512 placeholder devices for the production meshes.  The disabled pass is a
# CPU-backend artifact guard: XLA-CPU upcasts bf16 dots to f32 and LICM then
# hoists those converts out of the layer scan, materializing f32 copies of
# ALL stacked layer params/tape (+100s of GB at kimi-k2 scale).  On TPU bf16
# is MXU-native and no such converts exist, so disabling the hoist gives the
# memory profile the real machine would see.
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512",
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion",
)))

"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) cell and record memory/cost/collective analysis.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices form the production meshes
(single-pod 16×16, multi-pod 2×16×16); every cell must ``.lower().compile()``
under its real shardings.  Outputs per-cell JSON consumed by
benchmarks/roofline.py and EXPERIMENTS.md §Dry-run.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch qwen3-1.7b ...] [--shape train_4k ...] [--mesh single|multi|both]
      [--out results/dryrun.json] [--hlo-dir results/hlo]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import algos, configs
from repro.algos.dfa import DFAConfig
from repro.core import photonics
from repro.dist import sharding
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.serve.decode import cache_shardings, make_prefill, make_serve_step
from repro.train.optimizer import SGDM
from repro.utils import hlo_cost as hlo_cost_lib


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


VARIANT = {"name": "baseline"}  # mutated by main() — variant is process-wide


def _make_model(arch):
    if VARIANT["name"] == "opt" and arch.make_opt is not None:
        return arch.make_opt(jnp.bfloat16)
    return arch.make_model(jnp.bfloat16)


def _dfa_config() -> DFAConfig:
    # paper-system training config: off-chip BPD noise in the feedback path
    from repro.core.feedback import FeedbackConfig

    return DFAConfig(
        photonics=photonics.preset("offchip_bpd"), backend="ref",
        feedback=FeedbackConfig(dtype=jnp.bfloat16),
        # §Perf G1: norm scales frozen in the optimised variant — the
        # (B,S,D) all-reduces that exist only to feed them are DCE'd
        freeze_norms=(VARIANT["name"] == "opt"),
    )


def build_train(arch, mesh):
    model = _make_model(arch)
    cfg = _dfa_config()
    opt = SGDM(lr=0.01, momentum=0.9)
    algo = algos.get("dfa")
    vg = algo.value_and_grad(model, cfg)
    # §Perf K3: microbatch accumulation for the 1T cell — the DFA tape,
    # error tensor, logits and MoE transients all scale with the microbatch
    # (grads/optimizer state do not), trading a k× longer step for ~k× less
    # activation memory.  (K2, fusing the update into the backward map, was
    # REFUTED: old+new param/momentum stacks stay live inside the loop.)
    microbatches = 4 if (VARIANT["name"] == "opt"
                         and arch.name == "kimi-k2-1t-a32b") else 1

    def train_step(params, fb, opt_state, batch, seed):
        rng = jax.random.PRNGKey(seed)
        if microbatches == 1:
            (loss, _metrics), grads = vg(params, fb, batch, rng)
        else:
            split = lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:])
            mbs = jax.tree_util.tree_map(split, batch)

            def body(carry, xs):
                acc, lacc = carry
                micro, i = xs
                (l, _m), g = vg(params, fb, micro, jax.random.fold_in(rng, i))
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return (acc, lacc + l), None

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, p.dtype), params)
            (gsum, lsum), _ = jax.lax.scan(
                body, (zero, jnp.float32(0.0)),
                (mbs, jnp.arange(microbatches)))
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
        new_params, new_opt, _ = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    shape = configs.SHAPES["train_4k"]
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    fb_s = jax.eval_shape(
        lambda k: algo.init_extra_state(model, k, cfg), jax.random.PRNGKey(0)
    )
    opt_s = jax.eval_shape(opt.init, params_s)
    batch = dict(configs.token_specs(shape.global_batch, shape.seq_len))
    batch.update(arch.input_extras(shape.global_batch, "train"))
    seed = jax.ShapeDtypeStruct((), jnp.int32)

    params_sh = sharding.make_param_shardings(mesh, params_s)
    fb_sh = sharding.make_param_shardings(mesh, fb_s, sharding.FEEDBACK_RULES)
    opt_sh = sharding.make_param_shardings(mesh, opt_s)
    batch_sh = sharding.make_batch_shardings(mesh, batch)
    rep = sharding.replicated(mesh)

    fn = jax.jit(
        train_step,
        in_shardings=(params_sh, fb_sh, opt_sh, batch_sh, rep),
        out_shardings=(params_sh, opt_sh, rep),
        donate_argnums=(0, 2),
    )
    args = (params_s, fb_s, opt_s, batch, seed)
    extra = {"params": params_s, "model": model,
             "tokens": shape.global_batch * shape.seq_len, "kind": "train"}
    return fn, args, extra


def build_prefill(arch, mesh):
    model = _make_model(arch)
    shape = configs.SHAPES["prefill_32k"]
    prefill = make_prefill(model)
    batch = {"tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32)}
    batch.update(arch.input_extras(shape.global_batch, "prefill"))
    if arch.name == "whisper-small":
        # decoder prefill over target tokens; encoder consumes frame stubs
        batch["labels"] = batch["tokens"]  # unused by prefill, spec only
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = sharding.make_param_shardings(mesh, params_s)
    batch_sh = sharding.make_batch_shardings(mesh, batch)
    fn = jax.jit(prefill, in_shardings=(params_sh, batch_sh))
    extra = {"params": params_s, "model": model,
             "tokens": shape.global_batch * shape.seq_len, "kind": "prefill"}
    return fn, (params_s, batch), extra


def build_decode(arch, mesh, shape_name):
    model = _make_model(arch)
    shape = configs.SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches_s = jax.eval_shape(lambda: model.init_caches(b, s))
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    cache_len = jax.ShapeDtypeStruct((b,), jnp.int32)

    params_sh = sharding.make_param_shardings(mesh, params_s)
    caches_sh = cache_shardings(mesh, caches_s)
    batch_sh = sharding.make_batch_shardings(mesh, {"t": token})["t"]
    len_sh = sharding.make_batch_shardings(mesh, {"t": cache_len})["t"]
    rep = sharding.replicated(mesh)

    whisper = arch.name == "whisper-small"
    step = make_serve_step(model, whisper_enc=whisper)
    if whisper:
        enc = jax.ShapeDtypeStruct((b, model.cfg.n_frames, model.cfg.d_model), jnp.bfloat16)
        enc_sh = sharding.make_batch_shardings(mesh, {"t": enc})["t"]
        fn = jax.jit(step,
                     in_shardings=(params_sh, batch_sh, caches_sh, len_sh, enc_sh),
                     out_shardings=(batch_sh, rep, caches_sh),
                     donate_argnums=(2,))
        args = (params_s, token, caches_s, cache_len, enc)
    else:
        fn = jax.jit(step,
                     in_shardings=(params_sh, batch_sh, caches_sh, len_sh),
                     out_shardings=(batch_sh, rep, caches_sh),
                     donate_argnums=(2,))
        args = (params_s, token, caches_s, cache_len)
    extra = {"params": params_s, "model": model, "tokens": b, "kind": "decode"}
    return fn, args, extra


def run_cell(arch_name: str, shape_name: str, mesh_kind: str, hlo_dir=None) -> dict:
    arch = configs.get(arch_name)
    shape = configs.SHAPES[shape_name]
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "variant": VARIANT["name"]}

    if shape_name == "long_500k" and not arch.sub_quadratic:
        rec["status"] = "skip"
        rec["reason"] = ("full-attention arch: 512k dense-KV decode is "
                        "infeasible by design (DESIGN.md §6)")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.monotonic()  # duration measurement: immune to wall-clock steps
    try:
        with sharding.use_mesh(mesh):
            if shape.kind == "train":
                fn, args, extra = build_train(arch, mesh)
            elif shape.kind == "prefill":
                fn, args, extra = build_prefill(arch, mesh)
            else:
                fn, args, extra = build_decode(arch, mesh, shape_name)
            lowered = fn.lower(*args)
            compiled = lowered.compile()
    except Exception as ex:
        rec["status"] = "error"
        rec["reason"] = f"{type(ex).__name__}: {ex}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        rec["seconds"] = round(time.monotonic() - t0, 1)
        return rec

    rec["seconds"] = round(time.monotonic() - t0, 1)
    rec["status"] = "ok"
    rec["chips"] = int(mesh.devices.size)
    rec["tokens"] = extra["tokens"]
    rec["n_params"] = analysis.tree_param_count(extra["params"])
    rec["n_params_active"] = analysis.active_param_count(extra["params"], extra["model"])
    rec["param_bytes"] = analysis.tree_param_bytes(extra["params"])
    rec["cost"] = analysis.cost_analysis_dict(compiled)
    rec["memory"] = analysis.memory_analysis_dict(compiled)
    try:
        text = compiled.as_text()
        # trip-count-aware per-device accounting (XLA's cost_analysis counts
        # loop bodies once — see utils/hlo_cost.py)
        cost = hlo_cost_lib.analyze(text)
        rec["hlo_cost"] = cost.as_dict()
        rec["collectives"] = {
            "total_bytes": cost.collective_total,
            "total_count": int(sum(cost.coll_count.values())),
            "bytes_by_kind": dict(cost.coll_bytes),
            "count_by_kind": dict(cost.coll_count),
        }
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(
                    hlo_dir, f"{arch_name}__{shape_name}__{mesh_kind}.hlo.txt"), "w") as f:
                f.write(text)
        del text
    except Exception as ex:  # HLO text can be unavailable on some backends
        rec["collectives"] = {"error": str(ex)[:200]}
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=list(configs.ASSIGNED))
    ap.add_argument("--shape", nargs="*", default=list(configs.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--variant", choices=["baseline", "opt"], default="baseline")
    args = ap.parse_args()
    VARIANT["name"] = args.variant

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") == "ok"}

    for arch_name in args.arch:
        for shape_name in args.shape:
            for mesh_kind in meshes:
                key = (arch_name, shape_name, mesh_kind)
                if key in done:
                    print(f"[skip-done] {key}", flush=True)
                    continue
                print(f"[cell] {arch_name} × {shape_name} × {mesh_kind} …", flush=True)
                rec = run_cell(arch_name, shape_name, mesh_kind, args.hlo_dir)
                status = rec["status"]
                info = rec.get("reason", "")[:120] if status != "ok" else (
                    f"{rec.get('seconds', 0)}s "
                    f"flops={rec.get('cost', {}).get('flops', 0):.3g} "
                    f"coll={rec.get('collectives', {}).get('total_bytes', 0):.3g}B")
                print(f"  -> {status} {info}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"]) != key]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"done: {n_ok} ok, {n_skip} skip, {n_err} error", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
