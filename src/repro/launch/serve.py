"""Serving driver: ``python -m repro.launch.serve --arch qwen1.5-0.5b
--policy aging --lprs --apc``.

Full paper stack on real execution: chunked-prefill engine + Aging/FCFS/SJF
ordering + LPRS latency-targeted chunking (training its predictor on this
machine's own profiled latencies) + APC activity control.
"""
from __future__ import annotations

import argparse
import json


from repro.configs import get_config, tiny_config
from repro.core.apc import APCConfig
from repro.core.lprs import LPRSConfig
from repro.core.predictor import LatencyPredictor, PredictorConfig, bucket_and_downsample
from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig
from repro.core.slo import SLOConfig
from repro.tenancy.tenants import FairnessConfig, TenantSpec
from repro.engine.engine import EngineConfig, JAXEngine, serve
from repro.engine.kv_cache import pool_for_model
from repro.engine.workload import (
    WorkloadSpec,
    attach_prompt_tokens,
    sharegpt_like,
)
from repro.launch.compile_cache import place_compile_cache


def profile_and_train_predictor(
    model_cfg, engine: JAXEngine, *, n_requests: int = 48,
    budget: int = 128, epochs: int = 120, seed: int = 0,
) -> LatencyPredictor:
    """The paper's offline profiling pipeline (§3.2.1) on REAL latencies:
    run the static token-budget scheduler, record (features, wall ms),
    bucket + downsample, train the MLP."""
    reqs = sharegpt_like(WorkloadSpec(
        n_requests=n_requests, inter_arrival_s=0.005, max_context=256,
        max_new_tokens=32, seed=seed,
    ))
    attach_prompt_tokens(reqs, model_cfg.vocab_size, seed=seed)
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=budget,
                        max_seqs=engine.cfg.n_slots)
    )
    res = serve(reqs, sched, engine, collect_samples=True)
    feats, lats = res.samples
    keep, wts = bucket_and_downsample(feats[:, 12])  # scheduled_tokens col
    pred = LatencyPredictor(PredictorConfig(epochs=epochs))
    pred.fit(feats[keep], lats[keep], sample_weights=wts)
    print(f"predictor trained on {len(keep)} real samples: "
          f"{pred.evaluate(feats, lats)}")
    return pred


def robustness_from_args(args):
    """--failover / --chaos-seed -> a RobustnessConfig (or None: every serve
    path stays bit-identical to the fault-oblivious code)."""
    if not (args.failover or args.chaos_seed is not None):
        return None
    from repro.robustness import FaultInjector, FaultPlan, RobustnessConfig

    injector = None
    if args.chaos_seed is not None:
        plan = FaultPlan.fuzz(args.chaos_seed, n_faults=args.chaos_faults)
        injector = FaultInjector(plan)
    return RobustnessConfig(
        max_retries=args.max_retries,
        handoff_ttl_s=args.handoff_ttl if args.handoff_ttl > 0 else None,
        injector=injector,
    )


def run_disagg(args):
    """--disagg: build a prefill pool + decode pool fleet and serve the same
    workload through the cross-replica KV handoff path."""
    from repro.disagg import (
        DisaggConfig, HandoffCostConfig, build_disagg, serve_disagg,
    )

    model_cfg = get_config(args.arch) if args.full else tiny_config(args.arch)
    router = build_disagg(
        model_cfg,
        cfg=DisaggConfig(
            n_prefill=args.n_prefill,
            n_decode=args.n_decode,
            min_handoff_tokens=args.min_handoff_tokens,
            cost=HandoffCostConfig() if args.handoff_cost else None,
            robustness=robustness_from_args(args),
        ),
        engine_cfg=EngineConfig(
            n_slots=16, max_context=512, use_pallas=args.pallas,
            paged_kv=not args.dense_kv, pipelined=not args.sync_engine,
            pages_per_tile=args.pages_per_tile,
            kv_layout=args.kv_layout, buffering_depth=args.buffering_depth,
            preemption_mode=args.preemption_mode,
            nan_guard=args.nan_guard,
        ),
        sched_cfg=SchedulerConfig(
            policy=args.policy, alpha=args.alpha, beta=args.beta,
            token_budget=args.token_budget, max_seqs=16,
            apc=APCConfig(c_max=4, l_min=16) if args.apc else None,
        ),
        n_blocks=args.kv_blocks,
        prefix_cache=args.prefix_cache,
    )
    reqs = sharegpt_like(WorkloadSpec(
        n_requests=args.n_requests, inter_arrival_s=args.interval,
        max_context=256, max_new_tokens=48, seed=1,
    ))
    attach_prompt_tokens(reqs, model_cfg.vocab_size, seed=1)
    res = serve_disagg(reqs, router)
    router.check_invariants()

    if res.robustness is not None:
        rb = res.robustness
        print(f"  fault tolerance: died={rb.replicas_died} "
              f"failovers={rb.failovers} resumable={rb.recovered_resumable} "
              f"reprefill={rb.requeued_reprefill} "
              f"shed={rb.shed_replica_failure} "
              f"quarantined={rb.quarantined} faults_fired={rb.faults_fired}")
        for ev in rb.events:
            print(f"    {ev}")

    row = res.report.row()
    print(f"\n=== {args.arch} | DISAGG {args.n_prefill}P+{args.n_decode}D "
          f"policy={args.policy} kv={'dense' if args.dense_kv else 'paged'} "
          f"loop={'sync' if args.sync_engine else 'pipelined'} "
          f"cost={'model' if args.handoff_cost else 'always'} ===")
    print(f"finished {res.report.n_finished}/{res.report.n_total} "
          f"in {res.wall_s:.2f}s  ({res.rounds} rounds over "
          f"{len(router.replicas)} replicas)")
    print(f"  handoffs={res.handoffs} colocated={res.colocated} "
          f"dropped={res.dropped_handoffs} "
          f"moved={res.bytes_moved / 2**20:.1f} MiB")
    decode_prefill_tokens = sum(
        rs.sched.stats.scheduled_prefill_tokens for rs in router.decode)
    print(f"  decode-pool prefill tokens scheduled: {decode_prefill_tokens} "
          f"(handoffs resume decode-only)")
    for k, v in row.items():
        print(f"  {k:16s} {v*1e3 if 'e2e' in k or 'ttft' in k or 'prefill' in k or 'tpot' in k else v:10.2f}"
              + (" ms" if any(t in k for t in ("e2e", "ttft", "prefill", "tpot")) else ""))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({
                "report": row, "rounds": res.rounds, "wall_s": res.wall_s,
                "handoffs": res.handoffs, "colocated": res.colocated,
                "dropped_handoffs": res.dropped_handoffs,
                "bytes_moved": res.bytes_moved,
                "decode_prefill_tokens": decode_prefill_tokens,
            }, f)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--policy", default="aging", choices=["fcfs", "sjf", "aging"])
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--beta", type=float, default=-0.01)
    ap.add_argument("--token-budget", type=int, default=128)
    ap.add_argument("--n-requests", type=int, default=32)
    ap.add_argument("--interval", type=float, default=0.02)
    ap.add_argument("--lprs", action="store_true")
    ap.add_argument("--target-ms", type=float, default=0.0,
                    help="LPRS target latency (0 = auto from profiling median)")
    ap.add_argument("--apc", action="store_true")
    ap.add_argument("--pallas", action="store_true",
                    help="run the Pallas kernels instead of the jnp gather "
                         "oracles: compiled by Mosaic on a TPU, interpreted "
                         "(slowly) on the CPU; other platforms are refused")
    ap.add_argument("--dense-kv", action="store_true",
                    help="dense slot-indexed KV cache instead of the paged "
                         "block-table layout (A/B baseline; outputs are "
                         "identical under greedy sampling)")
    ap.add_argument("--sync-engine", action="store_true",
                    help="synchronous round loop instead of the overlapped "
                         "schedule/execute pipeline (A/B baseline; outputs "
                         "are identical under greedy sampling)")
    ap.add_argument("--pages-per-tile", type=int, default=1,
                    help="physical pages gathered per paged-attention K/V "
                         "tile (MXU efficiency at small page sizes)")
    ap.add_argument("--kv-layout", default="split",
                    choices=["split", "fused"],
                    help="paged KV pool layout: 'split' keeps separate K and "
                         "V pools; 'fused' interleaves K/V on the head axis "
                         "so one gather per page feeds both operands "
                         "(greedy outputs are identical)")
    ap.add_argument("--buffering-depth", type=int, default=1,
                    help="page-DMA buffering depth in the paged attention "
                         "kernels: depth N issues tile t+N-1's gather before "
                         "waiting on tile t, overlapping copies with compute "
                         "(greedy outputs are identical at any depth)")
    ap.add_argument("--preemption-mode", default="recompute",
                    choices=["recompute", "swap"],
                    help="KV-pressure eviction strategy: 'recompute' discards "
                         "the victim's KV and re-prefills it; 'swap' stages "
                         "it host-side and restores it on re-schedule "
                         "(chosen per victim by the transfer-vs-FLOPs cost "
                         "model; greedy outputs are identical either way)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the hash-based KV prefix cache (block-aligned "
                         "prompt reuse; hits skip the matched prefill compute)")
    ap.add_argument("--kv-blocks", type=int, default=2048,
                    help="KV pool size in blocks")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: separate prefill and decode "
                         "replica pools with cross-replica KV handoff "
                         "(greedy outputs are identical to single-engine)")
    ap.add_argument("--n-prefill", type=int, default=1,
                    help="prefill-pool replicas (with --disagg)")
    ap.add_argument("--n-decode", type=int, default=1,
                    help="decode-pool replicas (with --disagg)")
    ap.add_argument("--min-handoff-tokens", type=int, default=0,
                    help="prompts with fewer resident KV tokens than this "
                         "never migrate (with --disagg)")
    ap.add_argument("--handoff-cost", action="store_true",
                    help="price each handoff against colocated contention "
                         "instead of always migrating (with --disagg)")
    ap.add_argument("--ttft-slo", type=float, default=0.0,
                    help="time-to-first-token SLO in seconds for the serving "
                         "tenant (0 = off).  Setting either SLO enables the "
                         "SLO tier: deadline-aware LPRS targets, urgency-"
                         "ordered batching, SLO-weighted victim selection, "
                         "and load shedding of infeasible deadlines")
    ap.add_argument("--e2e-slo", type=float, default=0.0,
                    help="end-to-end completion SLO in seconds for the "
                         "serving tenant (0 = off; see --ttft-slo)")
    ap.add_argument("--failover", action="store_true",
                    help="fault-tolerant serving: replica health tracking, "
                         "crash unwinds, and (with --disagg) failover of a "
                         "dead replica's requests onto survivors")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="re-placements per request across replica failures "
                         "before a terminal shed (with --failover)")
    ap.add_argument("--handoff-ttl", type=float, default=0.0,
                    help="reap staged handoff records older than this many "
                         "seconds (0 = no TTL; with --failover)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="per-round finite-logits check: requests whose "
                         "logits go NaN/Inf are quarantined (terminal shed "
                         "reason 'numerics') instead of poisoning the batch")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="fuzz a deterministic fault plan from this seed and "
                         "inject it (implies --failover)")
    ap.add_argument("--chaos-faults", type=int, default=3,
                    help="number of faults in the fuzzed plan (--chaos-seed)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    place_compile_cache()

    if args.disagg:
        return run_disagg(args)

    model_cfg = get_config(args.arch) if args.full else tiny_config(args.arch)
    engine = JAXEngine(model_cfg, EngineConfig(
        n_slots=16, max_context=512, use_pallas=args.pallas,
        paged_kv=not args.dense_kv, pipelined=not args.sync_engine,
        pages_per_tile=args.pages_per_tile,
        kv_layout=args.kv_layout, buffering_depth=args.buffering_depth,
        preemption_mode=args.preemption_mode,
        nan_guard=args.nan_guard,
    ))

    predictor = None
    lprs_cfg = None
    if args.lprs:
        predictor = profile_and_train_predictor(model_cfg, engine)
        target = args.target_ms
        if target <= 0:
            target = 30.0
        lprs_cfg = LPRSConfig(target_latency_ms=target, search_delta=32)

    fairness_cfg = None
    slo_cfg = None
    if args.ttft_slo > 0 or args.e2e_slo > 0:
        # SLO tier: the workload's single "default" tenant carries the
        # deadlines; fairness is required (the tracker lives on its registry)
        fairness_cfg = FairnessConfig(tenants=(TenantSpec(
            "default",
            ttft_slo_s=args.ttft_slo if args.ttft_slo > 0 else None,
            e2e_slo_s=args.e2e_slo if args.e2e_slo > 0 else None,
        ),))
        slo_cfg = SLOConfig()

    sched = ChunkedPrefillScheduler(
        SchedulerConfig(
            policy=args.policy, alpha=args.alpha, beta=args.beta,
            token_budget=args.token_budget, max_seqs=16,
            lprs=lprs_cfg,
            apc=APCConfig(c_max=4, l_min=16) if args.apc else None,
            fairness=fairness_cfg,
            slo=slo_cfg,
        ),
        predictor=predictor,
    )

    reqs = sharegpt_like(WorkloadSpec(
        n_requests=args.n_requests, inter_arrival_s=args.interval,
        max_context=256, max_new_tokens=48, seed=1,
    ))
    attach_prompt_tokens(reqs, model_cfg.vocab_size, seed=1)
    kv_pool = pool_for_model(model_cfg, n_blocks=args.kv_blocks,
                             enable_prefix_cache=args.prefix_cache)
    res = serve(reqs, sched, engine, kv_pool=kv_pool, collect_samples=False,
                robustness=robustness_from_args(args))

    row = res.report.row()
    print(f"\n=== {args.arch} | policy={args.policy} lprs={args.lprs} "
          f"apc={args.apc} pallas={args.pallas} "
          f"kv={'dense' if args.dense_kv else 'paged'}"
          f"{'' if args.dense_kv else f'/{args.kv_layout}/d{args.buffering_depth}'} "
          f"loop={'sync' if args.sync_engine else 'pipelined'} "
          f"prefix_cache={args.prefix_cache} "
          f"preempt={args.preemption_mode} ===")
    print(f"finished {res.report.n_finished}/{res.report.n_total} "
          f"in {res.wall_s:.2f}s  ({res.rounds} rounds)")
    if res.robustness is not None:
        rb = res.robustness
        print(f"  fault tolerance: crash_unwinds={rb.crash_unwinds} "
              f"quarantined={rb.quarantined} faults_fired={rb.faults_fired}")
    for k, v in row.items():
        print(f"  {k:16s} {v*1e3 if 'e2e' in k or 'ttft' in k or 'prefill' in k or 'tpot' in k else v:10.2f}"
              + (" ms" if any(t in k for t in ("e2e", "ttft", "prefill", "tpot")) else ""))
    mem = res.memory
    if mem is not None:
        print(f"  kv: hit_rate={mem.cache_hit_rate:.2%} "
              f"hit_tokens={mem.cache_hit_tokens} evictions={mem.evictions} "
              f"preemptions={mem.preemptions} cached_blocks={mem.cached_blocks}")
        if mem.swap_preemptions:
            print(f"  swap: {mem.swap_preemptions} victims staged "
                  f"({mem.swapped_out_tokens} tokens out, "
                  f"{mem.swapped_in_tokens} restored over "
                  f"{mem.swap_restores} swap-ins)")
    if res.slo is not None:
        for t, rep in res.slo.per_tenant.items():
            print(f"  slo[{t}]: attained={rep.attained} "
                  f"violated={rep.violated} shed={rep.shed} "
                  f"attainment={rep.attainment:.2%} "
                  f"p50_ttft_slack={rep.ttft_slack_s['p50'] * 1e3:.1f} ms")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"report": row, "rounds": res.rounds, "wall_s": res.wall_s,
                       "memory": mem.row() if mem is not None else None,
                       "slo": res.slo.row() if res.slo is not None else None}, f)


if __name__ == "__main__":
    main()
