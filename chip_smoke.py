#!/usr/bin/env python3
"""Run the system's paths end to end on one TPU chip, in one process.

    python chip_smoke.py

1. Device gate: prints platform, device kind and device count, and exits
   non-zero unless the first device is a TPU. There is no CPU path.
2. LM serving at published width: ``olmo_1b`` (16 layers, d_model 2048,
   vocab 50304, f32 params, bf16 compute) through ``repro.launch.serve``.
   A batch of 4 requests (128-token prompts, 32 greedy new tokens) is
   answered twice, cold then warm. The engine's logits are checked against
   the uncached ``model_zoo.forward`` on the same tokens, teacher-forced
   (``repro.launch.serve.check_against_forward``: max and mean bounds).
3. Pallas kernels compiled (``interpret=False``) at one realistic shape
   each (``repro.kernels.cases``), against their oracles.
4. The mapping server: ``MappingHTTPServer`` in-process on an ephemeral
   localhost port; a cold request and its repeat for ``resnet18`` and for
   one zoo scenario. Repeats must be memo replays with a byte-identical
   ``frontier_json``. This phase is host numpy and uses no device.

JAX's persistent compilation cache is on (``repro.launch.compile_cache``);
the script reports compile seconds and cache hits so that a second run
shows what the cache saved. The last line of standard output is one JSON
object, printed only when every phase passed.

Nothing here starts a child process: the chip belongs to this one.
"""
import json
import os
import sys
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu otherwise writes its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SERVE_ARGV = ["--arch", "olmo_1b", "--batch", "4", "--prompt-len", "128",
              "--new-tokens", "32"]

MAPPING_REQUESTS = (
    {"network": "resnet18", "explorer": "grid", "budget": 4,
     "n_candidates": 4, "max_steps": 1024},
    {"network": "granite_moe_1b_a400m:decode@4096", "explorer": "grid",
     "budget": 4},
)


class CompileMeter:
    """Sums backend compile seconds and persistent-cache hits, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles={self.count} compile_s={self.seconds:.3f} "
                f"cache_hits={self.cache_hits}")


def device_gate():
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing was run",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def serve_phase(argv) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve as serve_launch

    args = serve_launch.parse_args(argv)
    eng = serve_launch.build_engine(args)
    cfg = eng.cfg
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(eng.params))
    print(f"serve: arch={cfg.arch_id} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab} params={n_params} "
          f"param_dtype={cfg.param_dtype} compute_dtype={cfg.compute_dtype}")
    prompts = serve_launch.make_prompts(eng, args)
    runs = []
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        toks, logits = eng.generate_with_logits(prompts)
        logits.block_until_ready()
        wall = time.perf_counter() - t0
        runs.append((toks, logits))
        print(f"serve: {label} wall_s={wall:.6f} "
              f"tokens_per_s={toks.size / wall:.3f}")
    (toks, logits), (toks_w, logits_w) = runs
    same = bool(np.array_equal(toks, toks_w)) and bool(
        jnp.array_equal(logits, logits_w))
    check = serve_launch.check_against_forward(eng, prompts, toks, logits)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"serve: logits_shape={tuple(logits.shape)} "
          f"finite={check['finite']} "
          f"max_abs_logit={check['max_abs_logit']:.6f} "
          f"max_abs_dlogit={check['max_abs_dlogit']:.6f} "
          f"bound={serve_launch.LOGIT_MAX_BOUND} "
          f"mean_abs_dlogit={check['mean_abs_dlogit']:.6f} "
          f"bound={serve_launch.LOGIT_MEAN_BOUND} "
          f"argmax_agreement={check['argmax_agreement']:.6f} "
          f"cold_warm_identical={same}")
    print(f"serve: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return (check["ok"] and same
            and toks.shape == (args.batch, args.new_tokens))


def kernel_phase() -> bool:
    import jax.numpy as jnp
    from repro.kernels.cases import check, kernel_cases

    ok = True
    for case in kernel_cases():
        t0 = time.perf_counter()
        _, out, err = check(case)
        good = (out.shape == case.args[0].shape
                and bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
                and err <= case.rel_tol)
        ok &= good
        print(f"kernel: {case.name} shape={tuple(out.shape)} "
              f"rel_err={err:.6e} bound={case.rel_tol} ok={good} "
              f"first_call_s={time.perf_counter() - t0:.3f} "
              f"({case.source})")
    return ok


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url + "/v1/mapping", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def mapping_phase() -> bool:
    from repro.serve import MappingHTTPServer, MappingService

    print("mapping: this phase runs on the host (numpy); no device work")
    server = MappingHTTPServer(MappingService(), port=0).start()
    ok = True
    try:
        for body in MAPPING_REQUESTS:
            t0 = time.perf_counter()
            cold = _post(server.url, body)
            t1 = time.perf_counter()
            warm = _post(server.url, body)
            t2 = time.perf_counter()
            good = (cold["status"] == "ok" and cold["best"] is not None
                    and cold["served_from"] == "search"
                    and warm["served_from"] == "memo"
                    and warm["frontier_json"] == cold["frontier_json"])
            ok &= good
            print(f"mapping: {body['network']} cold "
                  f"served_from={cold['served_from']} "
                  f"evaluated={cold['evaluated']} "
                  f"frontier={len(cold['frontier_points'])} "
                  f"host_s={t1 - t0:.6f}; repeat "
                  f"served_from={warm['served_from']} "
                  f"frontier_identical="
                  f"{warm['frontier_json'] == cold['frontier_json']} "
                  f"host_s={t2 - t1:.6f} ok={good}")
    finally:
        server.close()
    return ok


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = device_gate()
    meter = CompileMeter()
    print(f"compile cache: {cache_dir}")
    failed = []
    for name, phase in (("serve", lambda: serve_phase(SERVE_ARGV)),
                        ("kernels", kernel_phase),
                        ("mapping", mapping_phase)):
        t0 = time.perf_counter()
        try:
            ok = phase()
        except Exception:
            traceback.print_exc()
            ok = False
        print(f"phase {name}: {'ok' if ok else 'FAILED'} "
              f"wall_s={time.perf_counter() - t0:.3f} {meter.line()}",
              flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
