#!/usr/bin/env python
"""Serving SLO soak CLI: drive an N-replica serve fleet through a
seeded serve-profile chaos plan under closed-loop traffic and print the
JSON verdict (exit 0 iff every invariant held). The default
configuration is the full serving tier — paged KV blocks + radix
prefix cache + speculative decoding — so this soak is the regression
harness for those paths; `--no-prefix-cache` / `--spec-k 0` peel the
layers back off.

    python tools/serve_soak.py --replicas 3 --clients 6 --seed 7
    python tools/serve_soak.py --plan my_serve_plan.json --out /tmp/s1
    python tools/serve_soak.py --processes --replicas 2 --seed 7

`--processes` switches to the MULTI-PROCESS fleet soak: replicas are
real worker OS processes (horovod_tpu/serve/worker.py) behind a
ProcessFleetRouter, the seeded plan SIGKILLs one worker mid-traffic
and fires conn_reset/flaky blips on the dispatch wire, and the verdict
additionally asserts blips absorbed with zero failovers, replayed
dispatches deduped, and the respawned victim re-admitted on the newest
published weight version.

The verdict (stdout, one JSON object) carries the evidence for each
invariant: no_silent_drops, answered_once, shed_carry_retry_after,
kv_containment (+ injected/detected counts), failover_bounded
(+ failover_s), slo_held (+ p99_outside_ms / error_rate_outside),
capacity_restored, plus the resolved plan for reproduction. See
docs/serving.md (failover + SLO soak) and docs/chaos.md (serve.*
fault sites) for recipes.

SIGTERM drains the fleet (stop admitting, finish the in-flight tail,
answer stragglers with retry-after) before the process dies — the
orderly-shutdown leg of the no-silent-drop contract.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replicas", type=int, default=3,
                   help="fleet size (default 3)")
    p.add_argument("--clients", type=int, default=6,
                   help="closed-loop client threads (default 6)")
    p.add_argument("--seed", type=int, default=0,
                   help="plan seed (same seed => same fault schedule)")
    p.add_argument("--plan", default="random",
                   help="'random' (seeded serve profile) or a path to "
                        "a plan JSON")
    p.add_argument("--steps", type=int, default=240,
                   help="scheduler-iteration horizon the plan lands in")
    p.add_argument("--suspect-s", type=float, default=None,
                   help="heartbeat age past which a replica is ejected "
                        "(default 1.0 in-process, 2.0 with --processes "
                        "— cross-process heartbeats on a small box "
                        "need the margin)")
    p.add_argument("--slo-p99-ms", type=float, default=15000.0,
                   help="p99 latency bound outside recovery windows")
    p.add_argument("--slo-error-rate", type=float, default=0.02,
                   help="error-rate bound outside recovery windows")
    p.add_argument("--recovery-window", type=float, default=6.0,
                   help="seconds after each fault excluded from SLO")
    p.add_argument("--min-duration", type=float, default=8.0)
    p.add_argument("--max-duration", type=float, default=None,
                   help="soak wall-clock cap (default 45 in-process, "
                        "150 with --processes — a respawn is a full "
                        "worker startup and the kill may fire late)")
    p.add_argument("--out", default=None,
                   help="dump events/requests/verdict into this dir")
    p.add_argument("--no-kv-crc", action="store_true",
                   help="disable the KV crc ledger (the corrupt "
                        "invariant will fail — for demonstration only)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the radix prefix cache")
    p.add_argument("--spec-k", type=int, default=None,
                   help="speculative draft depth (0 disables the "
                        "drafter; default 3 in-process, 0 with "
                        "--processes — worker startup cost)")
    p.add_argument("--processes", action="store_true",
                   help="MULTI-PROCESS fleet soak: replicas are real "
                        "worker OS processes behind a "
                        "ProcessFleetRouter; the seeded plan SIGKILLs "
                        "one worker and blips the dispatch wire "
                        "(docs/serving.md, process-fleet section)")
    p.add_argument("--spawn-timeout", type=float, default=120.0,
                   help="--processes: seconds to wait for a worker "
                        "process to register ready")
    p.add_argument("--disagg", action="store_true",
                   help="DISAGGREGATED soak: --prefill + --decode "
                        "worker processes behind a DisaggRouter; the "
                        "seeded plan SIGKILLs a prefill worker "
                        "mid-migration and fires serve.migrate "
                        "conn_reset/corrupt at the KV-block push "
                        "(docs/serving.md, disaggregation section)")
    p.add_argument("--prefill", type=int, default=2,
                   help="--disagg: prefill pool size (default 2)")
    p.add_argument("--decode", type=int, default=1,
                   help="--disagg: decode pool size (default 1)")
    p.add_argument("--autoscale", action="store_true",
                   help="AUTOSCALE soak: a 1+1 disaggregated fleet "
                        "behind a live Autoscaler driven with phased "
                        "bursty traffic; both pools must scale up AND "
                        "back down with zero dropped sequences and "
                        "newcomers admitted on the newest weights "
                        "(docs/autoscale.md)")
    p.add_argument("--max-replicas", type=int, default=2,
                   help="--autoscale: per-pool ceiling (default 2)")
    p.add_argument("--no-chaos", action="store_true",
                   help="--autoscale: skip the autoscale-profile chaos "
                        "plan (scale events run unfaulted)")
    p.add_argument("--kv-tier", action="store_true",
                   help="FLEET-KV-TIER soak: multi-turn conversations "
                        "with a shared system prefix over a 2-replica "
                        "fleet running the HBM->host->disk eviction "
                        "ladder + fleet radix index, under the seeded "
                        "kvtier chaos profile (corrupt/drop on "
                        "demote/promote); asserts cross-replica hits, "
                        "bit-identical tokens and crc containment "
                        "(docs/serving.md, fleet-KV-tier section)")
    args = p.parse_args(argv)

    # one fleet on CPU devices; keep the run reproducible
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.kv_tier:
        from horovod_tpu.serve.soak import run_kvtier_soak
        verdict = run_kvtier_soak(
            args.out,
            replicas=2 if args.replicas == 3 else max(args.replicas, 2),
            clients=args.clients, seed=args.seed,
            plan=args.plan if args.plan != "random" else None,
            steps=args.steps if args.steps != 240 else 8,
            suspect_s=1.0 if args.suspect_s is None else args.suspect_s,
            min_duration_s=args.min_duration,
            max_duration_s=args.max_duration or 60.0)
        print(json.dumps(verdict, indent=2, sort_keys=True,
                         default=str))
        return 0 if verdict.get("ok") else 1

    if args.autoscale:
        from horovod_tpu.serve.soak import run_autoscale_soak
        verdict = run_autoscale_soak(
            args.out, clients=args.clients, seed=args.seed,
            plan=None if args.no_chaos else args.plan,
            suspect_s=2.0 if args.suspect_s is None else args.suspect_s,
            slo_p99_ms=args.slo_p99_ms,
            slo_error_rate=args.slo_error_rate,
            recovery_window_s=max(args.recovery_window, 8.0),
            max_duration_s=(240.0 if args.max_duration is None
                            else args.max_duration),
            max_replicas=args.max_replicas,
            spawn_timeout_s=args.spawn_timeout)
        json.dump(verdict, sys.stdout, indent=2, sort_keys=True)
        print()
        return 0 if verdict["ok"] else 1

    if args.disagg:
        from horovod_tpu.serve.soak import run_disagg_soak
        verdict = run_disagg_soak(
            args.out, prefill=args.prefill, decode=args.decode,
            clients=args.clients, seed=args.seed,
            plan=None if args.plan == "random" else args.plan,
            steps=args.steps,
            suspect_s=2.0 if args.suspect_s is None else args.suspect_s,
            slo_p99_ms=args.slo_p99_ms,
            slo_error_rate=args.slo_error_rate,
            recovery_window_s=args.recovery_window,
            min_duration_s=args.min_duration,
            max_duration_s=(180.0 if args.max_duration is None
                            else args.max_duration),
            spec_k=0 if args.spec_k is None else args.spec_k,
            kv_crc=False if args.no_kv_crc else None,
            prefix_cache=False if args.no_prefix_cache else None,
            spawn_timeout_s=args.spawn_timeout)
        json.dump(verdict, sys.stdout, indent=2, sort_keys=True)
        print()
        return 0 if verdict["ok"] else 1

    if args.processes:
        from horovod_tpu.serve.soak import run_fleet_soak
        verdict = run_fleet_soak(
            args.out, replicas=args.replicas, clients=args.clients,
            seed=args.seed,
            plan=None if args.plan == "random" else args.plan,
            steps=args.steps,
            suspect_s=2.0 if args.suspect_s is None else args.suspect_s,
            slo_p99_ms=args.slo_p99_ms,
            slo_error_rate=args.slo_error_rate,
            recovery_window_s=args.recovery_window,
            min_duration_s=args.min_duration,
            max_duration_s=(150.0 if args.max_duration is None
                            else args.max_duration),
            spec_k=0 if args.spec_k is None else args.spec_k,
            kv_crc=False if args.no_kv_crc else None,
            prefix_cache=False if args.no_prefix_cache else None,
            spawn_timeout_s=args.spawn_timeout)
        json.dump(verdict, sys.stdout, indent=2, sort_keys=True)
        print()
        return 0 if verdict["ok"] else 1

    from horovod_tpu.serve.soak import run_serve_soak
    verdict = run_serve_soak(
        args.out, replicas=args.replicas, clients=args.clients,
        seed=args.seed,
        plan=None if args.plan == "random" else args.plan,
        steps=args.steps,
        suspect_s=1.0 if args.suspect_s is None else args.suspect_s,
        slo_p99_ms=args.slo_p99_ms,
        slo_error_rate=args.slo_error_rate,
        recovery_window_s=args.recovery_window,
        min_duration_s=args.min_duration,
        max_duration_s=(45.0 if args.max_duration is None
                        else args.max_duration),
        kv_crc=False if args.no_kv_crc else None,
        prefix_cache=False if args.no_prefix_cache else None,
        spec_k=3 if args.spec_k is None else args.spec_k,
        sigterm_drain=True)
    json.dump(verdict, sys.stdout, indent=2, sort_keys=True)
    print()
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
