"""Bring-up check on one TPU: the serving engine at minicpm-2b's full width.

    python chip_smoke.py

Builds the engine exactly as `python -m repro.launch.serve --engine` does —
two tenants (random minicpm-2b weights from a seed, held in bf16 on the
device, and a perturbed variant of them), paged KV in 16-token pages,
chunked prefill with bucketing, the Pallas paged-decode kernel, and a
weight arena of n_layers+1 slots so that tenant switches run the install
path — then serves 8 greedy requests, 4 per tenant, and checks that:

  * every request finishes with exactly its token count, all in vocab;
  * the decode step the engine runs holds the compiled kernel
    (`tpu_custom_call`), not an XLA fallback;
  * the kernel on the chip, at the engine's shapes, matches the jnp
    reference `kernels/paged_attention/ref.py` within KERNEL_TOL;
  * the decode logits of a live engine state are finite.

It prints device, set-up, compile, memory and serving figures, and as its
last line one JSON object naming the device.  Without a TPU, or without
the repository's `src/` beside it, it exits non-zero and prints no result.
"""
from __future__ import annotations

import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
# the run's Chrome trace (gitignored); it also feeds the step-component
# breakdown printed below
OUT_DIR = Path(__file__).resolve().parent / "smoke_out"

ARCH = "minicpm-2b"
N_REQUESTS = 8                  # alternating tenants: 4 each
PROMPT_LO, PROMPT_HI = 64, 512  # prompt lengths, uniform from the seed
GEN = 32                        # new tokens per request
SEED = 0
# bf16 pools and queries, float32 accumulation on both sides: the same
# tolerance the interpret-mode kernel tests hold bf16 to
KERNEL_TOL = 2e-2
MAX_STEPS = 4096                # far above the ~100 steps 8 requests need


def serve_argv(arch: str = ARCH, *, smoke: bool = False,
               prompt_hi: int = PROMPT_HI, gen: int = GEN, page: int = 16,
               chunk: int = 128, turn_steps: int = 64,
               trace_out: str = "") -> list:
    """`repro.launch.serve` arguments of the smoke's engine.  The default
    turn outlasts a tenant's whole batch, so the run switches tenants once,
    when tenant-a drains: at full width the first install of one tenant
    over the other costs each of its ~1,640 (occupant, layer) pairs on the
    host, and every further first-time switch direction costs as much."""
    argv = ["--arch", arch, "--engine", "--kv-layout", "paged",
            "--page-size", str(page), "--kernel-backend", "pallas",
            "--prefill-chunk", str(chunk), "--prompt-len", str(prompt_hi),
            "--gen", str(gen), "--kv-slots", "4",
            "--turn-steps", str(turn_steps)]
    if smoke:
        argv.append("--smoke")
    if trace_out:
        argv += ["--trace-out", trace_out]
    return argv


def _host_peak_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def run(argv: list, *, n_requests: int = N_REQUESTS,
        prompt_lo: int = PROMPT_LO, seed: int = SEED, log=print) -> dict:
    """Build the engine from serve.py's own parser and `build_engine`, serve
    `n_requests` seeded greedy requests, and gather what the checks read.
    Platform-neutral: the TPU-only verdicts are made by `main`."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import interpret_mode
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.launch import serve
    from repro.launch.steps import (cached_fused_paged_serve_step,
                                    make_paged_serve_step)
    from repro.serving import RequestStatus, format_summary

    args = serve.build_parser().parse_args(argv)
    log(f"building the engine: {' '.join(argv)}")
    t0 = time.perf_counter()
    eng = serve.build_engine(args)
    build_s = time.perf_counter() - t0
    names = list(eng.models)
    cfg = eng.models[names[0]].cfg
    codes_mb = sum(lay.codes.size for lay in eng.residency.store.layers) / 1e6
    log(f"engine construction: {build_s:.3f} s (2 tenants x {cfg.name}, "
        f"{cfg.n_layers} layers, d_model {cfg.d_model})")
    log(f"residency store: {eng.residency_build_s:.3f} s to quantize "
        f"{len(eng.residency.store.layers)} layers into {codes_mb:.1f} MB "
        "of codes")
    log(f"host peak RSS after construction: {_host_peak_rss_gib():.2f} GiB")

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(prompt_lo, args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab, plen).tolist()
        reqs.append(eng.submit(names[i % len(names)], prompt,
                               max_new_tokens=args.gen))

    # the engine's decode program minus fused sampling: logits come back,
    # caches are not donated, so probing a live state leaves it intact
    probe = jax.jit(make_paged_serve_step(cfg, "pallas", interpret_mode()))
    logits_finite = None
    # a tenant switch: the weight arena (n_layers+1 slots) holds one
    # tenant at a time, and another one becomes the resident tenant
    switches, resident, steps = 0, None, 0
    t0 = time.perf_counter()
    while eng.has_work():
        if logits_finite is None:
            for name in names:
                arena = eng.arenas[name]
                if any(eng.requests[arena.owner_of(s)].status
                       is RequestStatus.RUNNING
                       for s in arena.active_slots()):
                    tokens, pos, tables = arena.decode_inputs()
                    logits, _ = probe(eng.models[name].params, tokens,
                                      arena.caches, pos, tables)
                    logits_finite = bool(jnp.all(jnp.isfinite(logits)))
                    del logits, _
                    break
        eng.step()
        steps += 1
        now = [n for n in names if eng.residency.is_resident(n)]
        if len(now) == 1 and now[0] != resident:
            switches += resident is not None
            resident = now[0]
        if steps > MAX_STEPS:
            raise RuntimeError(f"engine still busy after {MAX_STEPS} steps")
    serve_s = time.perf_counter() - t0
    summary = eng.summary(wall_s=serve_s)

    # the fused decode program the engine ran, lowered at its own inputs
    name = names[0]
    arena, params = eng.arenas[name], eng.models[name].params
    tokens, pos, tables = arena.decode_inputs()
    B = tokens.shape[0]
    lowered = cached_fused_paged_serve_step(
        cfg, "pallas", interpret_mode()).lower(
            params, tokens, arena.caches, pos, tables,
            jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.int32))
    decode_has_kernel = "tpu_custom_call" in lowered.as_text()
    decode_memory = lowered.compile().memory_analysis()

    # the kernel alone at the engine's shapes against the jnp reference
    H, D, Hkv = cfg.n_heads, cfg.head_dim, cfg.n_kv_heads
    n_pages, page = arena.allocator.n_pages + 1, arena.page_size
    T = tables.shape[1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv * D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv * D), jnp.bfloat16)
    tab = jax.random.randint(ks[3], (B, T), 1, n_pages, jnp.int32)
    kpos = jax.random.randint(ks[4], (B,), 0, T * page, jnp.int32)
    got = np.asarray(jax.jit(paged_attention)(q, kp[None], vp[None], tab,
                                              kpos, 0), np.float32)
    want = np.asarray(jax.jit(paged_attention_ref)(q, kp, vp, tab, kpos),
                      np.float32)
    excess = np.abs(got - want) - KERNEL_TOL * (1.0 + np.abs(want))

    return {
        "eng": eng, "reqs": reqs, "gen": args.gen, "vocab": cfg.vocab,
        "build_s": build_s, "serve_s": serve_s, "steps": steps,
        "switches": switches, "summary": summary,
        "summary_text": format_summary(summary),
        "logits_finite": logits_finite,
        "decode_has_kernel": decode_has_kernel,
        "decode_memory": decode_memory,
        "kernel_max_abs_err": float(np.max(np.abs(got - want))),
        "kernel_ok": bool(np.all(excess <= 0.0)),
        "kernel_shapes": dict(B=B, H=H, Hkv=Hkv, D=D, pages=n_pages,
                              page=page, T=T),
    }


def failures(res: dict, *, need_kernel: bool) -> list:
    """The checks every smoke run must pass; `need_kernel` adds the TPU
    verdict that the decode step holds the compiled kernel."""
    bad = []
    for r in res["reqs"]:
        if r.status.name != "FINISHED" or len(r.generated) != res["gen"]:
            bad.append(f"request {r.rid} ({r.model}): {r.status.name} with "
                       f"{len(r.generated)}/{res['gen']} tokens")
        elif not all(0 <= t < res["vocab"] for t in r.generated):
            bad.append(f"request {r.rid}: token outside the vocab")
    if res["switches"] < 1:
        bad.append("no tenant switch: the install path never ran")
    if res["logits_finite"] is not True:
        bad.append(f"decode logits finite: {res['logits_finite']}")
    if not res["kernel_ok"]:
        bad.append(f"kernel vs reference: max |err| "
                   f"{res['kernel_max_abs_err']:.4g} over tolerance "
                   f"{KERNEL_TOL} at {res['kernel_shapes']}")
    if need_kernel and not res["decode_has_kernel"]:
        bad.append("decode step has no tpu_custom_call: the Pallas kernel "
                   "did not run")
    return bad


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke.py: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {dev.platform!r} "
              "devices", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    compiles = {"n": 0, "s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")
    print(f"compile cache: {cache_dir}")
    OUT_DIR.mkdir(exist_ok=True)
    trace = OUT_DIR / "chip_smoke_trace.json"
    t_start = time.perf_counter()
    res = run(serve_argv(trace_out=str(trace)))
    eng = res["eng"]
    eng.tracer.export_chrome_trace(str(trace))

    m = res["decode_memory"]
    stats = dev.memory_stats() or {}
    n_tok = sum(len(r.generated) for r in res["reqs"])
    print(f"set-up: {res['build_s']:.3f} s; compiles over the whole run: "
          f"{compiles['n']} ({compiles['s']:.3f} s, "
          f"{compiles['cache_hits']} persistent-cache hits)")
    print(f"serve: {len(res['reqs'])} requests, {n_tok} tokens in "
          f"{res['serve_s']:.3f} s wall over {res['steps']} steps, "
          f"{res['switches']} tenant switches")
    print(res["summary_text"])
    comps = {k[len("component_"):-2]: v for k, v in res["summary"].items()
             if k.startswith("component_") and k.endswith("_s")}
    print("host seconds by step component: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(comps.items())))
    print(f"decode step memory_analysis: arguments "
          f"{m.argument_size_in_bytes / 2**30:.3f} GiB, outputs "
          f"{m.output_size_in_bytes / 2**30:.3f} GiB (aliased "
          f"{m.alias_size_in_bytes / 2**30:.3f}), temps "
          f"{m.temp_size_in_bytes / 2**30:.3f} GiB")
    print(f"device peak_bytes_in_use: "
          f"{stats.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB of "
          f"{stats.get('bytes_limit', 0) / 2**30:.3f} GiB")
    print(f"host peak RSS: {_host_peak_rss_gib():.2f} GiB")
    print(f"decode step holds tpu_custom_call: {res['decode_has_kernel']}")
    print(f"kernel vs reference at {res['kernel_shapes']}: max |err| "
          f"{res['kernel_max_abs_err']:.4g} (tolerance {KERNEL_TOL})")
    print(f"decode logits finite: {res['logits_finite']}")
    print(f"total: {time.perf_counter() - t_start:.3f} s; trace: {trace}")

    bad = failures(res, need_kernel=True)
    if bad:
        for b in bad:
            print(f"FAIL: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
