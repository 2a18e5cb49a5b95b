"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Batched prefill + greedy decode with the ServeEngine at the architecture's
published width (``--smoke``: the reduced CPU width); optionally schedules a
mixed request stream across two pools with the paper's CAB policy
(--heterogeneous), or replays an open request trace through GrIn-P placement
plus SLO admission control (--traffic).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model
from repro.serve.engine import (ServeEngine, init_serving_params,
                                request_service_fns)

_TRACE = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                      "examples", "data", "serve_trace.json")


def _parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced smoke_config width (CPU runs); "
                         "default is the published width")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--heterogeneous", action="store_true",
                    help="CAB-schedule a prefill/decode mix over two pools")
    ap.add_argument("--traffic", action="store_true",
                    help="replay an open request trace through GrIn-P "
                         "placement with SLO admission control")
    ap.add_argument("--trace", default=None,
                    help="request trace JSON (default: the bundled "
                         "examples/data/serve_trace.json)")
    ap.add_argument("--load", type=float, default=1.2,
                    help="offered load as a fraction of measured capacity "
                         "(--traffic; >1 = overload)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace of scheduler + admission "
                         "decisions here (--traffic; open in Perfetto or "
                         "summarize with tools/trace_view.py)")
    return ap.parse_args(argv)


def run(argv=None) -> dict:
    """Serve once as the command line says; returns what was served:
    the engine and its batch, the generated tokens, and (with
    --heterogeneous) the measured mu and per-policy closed-loop rates."""
    args = _parse(argv)
    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    model = build_model(cfg)
    engine = ServeEngine(model, init_serving_params(model,
                                                    jax.random.PRNGKey(0)),
                         max_len=args.prompt_len + args.steps + 8)
    result: dict = {"cfg": cfg, "engine": engine}

    key = jax.random.PRNGKey(1)
    if cfg.family == "audio":
        toks = jax.random.randint(
            key, (args.batch, cfg.n_codebooks, args.prompt_len), 0,
            cfg.vocab_size)
    else:
        toks = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                  cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            key, (args.batch, cfg.n_patches, cfg.d_model), jnp.bfloat16)

    t0 = time.time()
    out = jax.block_until_ready(engine.generate(batch, steps=args.steps))
    dt = time.time() - t0
    n_tok = int(np.prod(out.shape))
    print(f"[serve] {cfg.name}: generated {out.shape} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    print("[serve] sample:", np.asarray(out)[0].tolist()[:16])
    result.update(batch=batch, tokens=out)

    if args.heterogeneous:
        from repro.core import classify_2x2
        from repro.sched import SchedulerCore, get_policy
        from repro.sched.virtual import VirtualTimeCluster

        fns = request_service_fns(engine, batch, toks)
        vc = VirtualTimeCluster(fns)
        mu = vc.measure_rates(2, reps=3)
        print(f"[serve] measured mu:\n{np.round(mu, 2)} "
              f"({classify_2x2(mu).value})")
        types = [0] * 4 + [1] * 4
        result.update(mu=mu, throughput={})
        for name in ("cab", "lb"):
            sched = SchedulerCore(get_policy(name), mu)
            m = VirtualTimeCluster(fns).run_closed(
                sched, types, n_completions=60, warmup=10)
            result["throughput"][name] = m.throughput
            print(f"[serve] {sched.name}: X={m.throughput:.2f} req/s")

    if args.traffic:
        from repro.sched import SchedulerCore
        from repro.sched.priority import GrInPriorityPolicy
        from repro.sched.virtual import VirtualTimeCluster
        from repro.traffic import (AdmissionController, SLOClass, load_trace,
                                   replay_open)

        fns = request_service_fns(engine, batch, toks)
        vc = VirtualTimeCluster(fns)
        mu = vc.measure_rates(2, reps=3)
        print(f"[serve] measured mu:\n{np.round(mu, 2)}")
        # saturation knee given the trace's class mix: the load where the
        # busiest class fills its best pool; scale the trace so the offered
        # rate is --load x that
        times, classes = load_trace(args.trace or os.path.normpath(_TRACE))
        trace_rate = len(times) / float(times[-1] - times[0])
        shares = np.bincount(classes, minlength=2) / len(classes)
        x_knee = 1.0 / max(shares[c] / mu[c].max() for c in range(2))
        times = times * (trace_rate / (args.load * x_knee))
        qcap = 6
        rec = None
        if args.trace_out:
            from repro.obs import TraceRecorder
            rec = TraceRecorder()
        core = SchedulerCore(GrInPriorityPolicy((2.0, 1.0)), mu, recorder=rec)
        # SLOs: protect the interactive prefill class at its own service
        # plus 1.5x a worst-case head-of-line decode block (pools are FCFS);
        # the decode class is best-effort
        slo = (SLOClass(deadline=1.5 / mu[1].min() + 6.0 / mu[0].max(),
                        percentile=0.9, protected=True),
               SLOClass(deadline=60.0 / mu[1].max(), percentile=0.9))
        adm = AdmissionController(core, slo, class_of_type=[0, 1],
                                  queue_capacity=qcap, window=64,
                                  adapt_every=8)
        m = replay_open(vc, adm, times, classes, warmup=len(times) // 10)
        if rec is not None:
            n = rec.export(args.trace_out)
            print(f"[serve] wrote {n} trace events to {args.trace_out} "
                  f"({rec.dropped} dropped)")
        print(f"[serve] GrIn-P + admission @ load {args.load:.2f}: "
              f"goodput {m.throughput:.2f} req/s")
        for c, name in enumerate(("prefill", "decode")):
            print(f"[serve]   class {c} ({name}): done "
                  f"{int(m.class_completed[c])} shed {int(m.class_shed[c])} "
                  f"p50 {m.class_p50[c]:.3f}s p99 {m.class_p99[c]:.3f}s "
                  f"SLO-met {m.class_deadline_met[c]:.2f} "
                  f"limit {m.limits[c]:.0f}")
    return result


if __name__ == "__main__":
    run()
