"""Benchmark: aggregate BLS signature verification throughput per chip.

Workload (BASELINE.json north star): FastAggregateVerify over attestation
committees — the hot loop of process_attestation
(reference specs/phase0/beacon-chain.md:1742-1756, :719-735). A mainnet epoch
is 32 slots x 64 committees = 2048 aggregate verifications covering ~300k
attesting validators; the target is that epoch in < 2 s on a v5e-8, i.e.
~150k signatures/sec/pod = ~18.75k signatures/sec/chip.

`vs_baseline` is the ratio of measured signatures/sec/chip to the
single-chip north-star share (the reference publishes no numbers of its own
— BASELINE.md documents that absence).

Prints ONE final JSON line: {"metric", "value", "unit", "vs_baseline"} (+ a
"platform" note, and an "error" key instead of a traceback on failure).

Default mode: one process on the device JAX finds. It runs the committee
cell (N committee checks of K signers, N=32, K=128, median of 3 warm reps
after a compile-inclusive warmup) or, with BENCH_MODE=epoch, the epoch
replay (BASELINE config #4). BENCH_N, BENCH_K, BENCH_REPS and BENCH_MODE
override. The line names the platform it ran on. Unless JAX_PLATFORMS=cpu
was set, a run that finds no accelerator prints an error line and exits
non-zero: a CPU number is never reported under the chip metric. Any
failure exits non-zero with an error line.

`--mode serve` is separate from the committee/epoch machinery: it drives a
synthetic Poisson gossip load (duplicate-heavy, with an injected backend
failure) through the streaming VerificationService
(consensus_specs_tpu/serve/) in-process on CPU, and its JSON line carries
sustained signatures/sec plus the serving numbers — batch occupancy, cache
hit rate, p50/p95/p99 submit->result latency, and the prep-vs-device time
split per flush (knobs: SERVE_* env vars, see serve/load.py). Add
`--trace out.json` to record per-request spans (queue-wait/prep/device/
combine/finalize) + VM program executions and export Chrome trace-event
JSON (the flight-recorder lane included when it is armed); `--flight out.jsonl` arms the flight recorder
(obs/flight.py) and dumps its structured-event journal after the run;
SERVE_METRICS_PORT=<port|0> additionally serves Prometheus `/metrics` +
`/snapshot` + `/healthz` (now SLO-state-bearing) + `/flightdump` during
the run (obs/).

`--mode serve --mesh N` runs the same serve load with the verify plane
sharded over N virtual CPU devices (CONSENSUS_SPECS_TPU_MESH; the
micro-batch's Miller loops and RLC chunk ladders ride the mesh batch
axis, the combine's product folds cross-replica via the Fq12 ppermute
butterfly, and the flush still pays ONE final exponentiation).
`--mode serve-mesh` is the scaling sweep: one `--mode serve --mesh d`
child per device count (SERVE_MESH_DEVICES, default 1,2,4,8), emitting a
`mesh` section — per-count sigs/sec, mesh
fallbacks, efficiency vs single-device — that tools/bench_compare.py
gates on ok-state round over round (`make serve-bench-mesh`).

`--mode serve-fleet` is the multi-process fleet sweep (ISSUE 11): one
`serve/fleet.FleetRouter` fleet of REAL worker processes per worker
count (SERVE_FLEET_WORKERS, default 1,2,4), each worker core-pinned and
warmed at exactly the flush shapes its consistent-hash share of the
stream produces; the `fleet` JSON section carries aggregate sigs/sec per
count plus the merged-scrape exactness property (merged /metrics ==
exact merge of per-worker snapshots) and is state-gated round over
round by tools/bench_compare.py ("FLEET ERRORED"). The parent pays the
jax import (ops/__init__ loads it eagerly) but never does device work
or compiles — those happen only in the core-pinned workers.

`--mode codec` is the prep-only microbenchmark: the batched input codec
(ops/codec.py) vs the per-item pure-Python prep path, items/sec over
CODEC_ITEMS items per kind — no pairings, just the front-door cost.

`--mode rlc` is the final-exp microbenchmark: per-item easy+hard
finalization vs the random-linear-combination combine
(bls_backend.batch_verify_rlc's core) on identical Miller outputs,
items/sec across N in {4,16,64,256} (RLC_BENCH_* env).

`--mode sim` is the adversarial multi-node network simulation
(consensus_specs_tpu/sim/): every named scenario class — partition/heal,
latency skew, lossy links, equivocating proposals, withheld-block
orphans, long-range reorg attempts, censored aggregates — runs N
independent HeadService+VerificationService nodes over a deterministic
discrete-event gossip fabric, and the JSON line reports the matrix:
per-scenario convergence through the differential gate (every honest
head bit-identical to spec.get_head on the union view), partition
heal-to-convergence latency, per-node heads/sec, and the fault mix
(CONSENSUS_SPECS_TPU_SIM_* env knobs; the `sim` section is gated round
over round by tools/bench_compare.py — a newly diverging scenario fails).

`--mode soak` is the long-horizon telemetry soak (ISSUE 19,
consensus_specs_tpu/bench/soak.py): a thousand-plus-slot simnet
scenario (periodic partitions over a linear canonical chain) replayed
against real verdict-mode fleet workers, with a per-node
chain/health.py ledger observing every slot past warm-up, a sim-clock
obs/timeseries.py store recording the full gauge history, and the
stitched cross-process Chrome trace dumped at the end. The JSON line's
value is simulated slots/sec of wall time; `vs_baseline` is 1.0 iff the
health gate (participation floor, bounded finality lag, zero
unexplained reorgs) held on every node; the `health` section is
state-gated round over round by tools/bench_compare.py ("HEALTH
DIVERGED"). CONSENSUS_SPECS_TPU_SOAK_* env knobs size it.

`--mode proofs` is the light-client read-path bench
(consensus_specs_tpu/bench/proofs.py): 10^4-10^6 simulated clients
replayed against the ProofService — R distinct per-slot proof artifacts
(finality branch + next-sync-committee branch + signed LightClientUpdate,
every one verified through spec.validate_light_client_update AND
is_valid_merkle_branch against an independently re-Merkleized root)
behind the content-addressed (slot, state_root) cache. The JSON line's
value is proofs/sec; `vs_baseline` is the steady-state cache hit rate
(the >= 0.99 acceptance bar); the `proofs` section is state-gated round
over round by tools/bench_compare.py ("PROOFS DIVERGED" when a
previously-verified shape stops verifying). CONSENSUS_SPECS_TPU_PROOF_*
env knobs size it.

`--mode head` is the chain-plane bench: a synthetic fork-and-gossip
replay (consensus_specs_tpu/bench/head_replay.py) through the
HeadService + proto-array vs the spec-store `get_head` recompute, at
growing block-tree sizes (HEAD_TREE_SIZES). The JSON line's value is
proto-array heads/sec at the largest tree; `vs_baseline` is the measured
speedup over the spec path divided by the 10x acceptance bar; per-tree
numbers ride `per_mode_best` as `head[<blocks>]` keys so
tools/bench_compare.py diffs them round over round. Fault injection
(invalid-signature + withheld-block deferred gossip) comes from
serve/load.py; SERVE_METRICS_PORT exposes /metrics mid-replay and the
line records the `chain.*` scrape.
"""
import json
import os
import sys
import time


def _emit(value: float, vs_baseline: float, **extra) -> None:
    line = {
        "metric": "aggregate BLS signatures verified/sec/chip",
        "value": round(value, 2),
        "unit": "signatures/sec",
        "vs_baseline": round(vs_baseline, 4),
    }
    line.update(extra)
    print(json.dumps(line), flush=True)


def _emit_result(result: dict) -> None:
    _emit(result.pop("value"), result.pop("vs_baseline"), **result)


def _workload_params():
    """(n, k, reps, mode) of the default mode, env overrides applied."""
    return (
        int(os.environ.get("BENCH_N", "32")),
        int(os.environ.get("BENCH_K", "128")),
        int(os.environ.get("BENCH_REPS", "3")),
        os.environ.get("BENCH_MODE", "committee"),
    )


TARGET_PER_CHIP = 150_000 / 8  # north star: 300k sigs < 2 s on 8 chips


class NoAccelerator(RuntimeError):
    """An accelerator was asked for (JAX_PLATFORMS is not ``cpu``) and JAX
    found none."""


def device_info() -> dict:
    """The device this process runs on, as JAX reports it. Raises
    NoAccelerator when JAX resolved to the CPU without being told to."""
    import jax

    devices = jax.devices()
    if (devices[0].platform == "cpu"
            and os.environ.get("JAX_PLATFORMS", "") != "cpu"):
        raise NoAccelerator(
            "no accelerator found (JAX resolved to cpu); set "
            "JAX_PLATFORMS=cpu for an explicit CPU run")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def run_workload(emit_partial=None) -> dict:
    """Run the configured workload on the device this process found.
    Returns the final result dict (not yet printed); ``emit_partial`` is
    called with in-progress result dicts as they improve."""
    from consensus_specs_tpu.obs import programs as obs_programs
    from consensus_specs_tpu.ops import profiling

    profiling.reset()
    obs_programs.export_gauges()

    device = device_info()
    platform = device["platform"]
    n, k, reps, mode = _workload_params()

    if mode == "epoch":
        from consensus_specs_tpu.bench.epoch_replay import run_epoch_replay

        result = run_epoch_replay(emit_partial=emit_partial)
        result["device"] = device
        return result

    from consensus_specs_tpu.ops import bls_backend
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.bls12_381 import R

    privkeys = [i + 1 for i in range(k)]
    pubkeys = [bls.SkToPk(sk) for sk in privkeys]
    # an aggregate of same-message signatures equals one signature by the
    # summed secret key — setup is n signs, not n*k
    agg_sk = sum(privkeys) % R

    pubkey_sets, messages, signatures = [], [], []
    for i in range(n):
        msg = i.to_bytes(32, "little")
        pubkey_sets.append(pubkeys)
        messages.append(msg)
        signatures.append(bls.Sign(agg_sk, msg))

    def result(value, **extra):
        out = dict(
            value=value,
            vs_baseline=value / TARGET_PER_CHIP,
            platform=platform,
            device=device,
            mode="committee",
            n=n,
            k=k,
        )
        out.update(extra)
        return out

    # warmup: compiles the VM shape buckets (persisted via the XLA
    # compilation cache)
    t0 = time.perf_counter()
    got = bls_backend.batch_fast_aggregate_verify(
        pubkey_sets, messages, signatures
    )
    warm = time.perf_counter() - t0
    if not got.all():
        raise AssertionError("warmup verification failed")
    if emit_partial is not None:
        emit_partial(result(n * k / warm, stage="warmup (compile-inclusive)"))

    times = []
    for r in range(reps):
        t0 = time.perf_counter()
        got = bls_backend.batch_fast_aggregate_verify(
            pubkey_sets, messages, signatures
        )
        dt = time.perf_counter() - t0
        if not got.all():
            raise AssertionError("benchmark verification failed")
        times.append(dt)
        if emit_partial is not None:
            emit_partial(
                result(n * k / min(times), stage=f"rep {r + 1}/{reps}")
            )
    # median of reps: stabler than min against one lucky/cold rep
    times.sort()
    best = times[len(times) // 2] if times else warm

    final = result(n * k / best, compile_s=warm)
    if profiling.enabled():  # dynamic check: env flips after import count
        final["profile"] = profiling.summary()
        # per-program provenance: steps/regs/assembly source for every VM
        # program this run resolved — plus the vmlint analysis stats
        # (max_live, critical path, classification) when a vm_analysis
        # pass ran in this process (obs/programs.note_analysis)
        final["programs"] = obs_programs.registry_snapshot()["programs"]
    return final


def _cli_opt(name):
    """`<name> <v>` / `<name>=<v>` from argv."""
    argv = sys.argv[1:]
    for i, arg in enumerate(argv):
        if arg == name and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith(name + "="):
            return arg.split("=", 1)[1]
    return None


def _cli_mode():
    """`--mode <m>` / `--mode=<m>` from argv."""
    return _cli_opt("--mode")


def main():
    if _cli_mode() == "serve":
        # streaming serve-plane bench, in-process and CPU-forced: the
        # serve line's value is the service-layer numbers (occupancy,
        # cache hit rate, latency percentiles) on a CPU-sized load —
        # SERVE_* env vars scale it up
        # `--trace out.json` turns on the span tracer for the whole run
        # and exports Chrome trace-event JSON (pipeline spans + VM program
        # executions + per-program registry) after the load completes
        # `--flight out.jsonl` arms the flight recorder for the run and
        # dumps its JSONL journal afterwards (the on-demand forensic dump;
        # the recorder also auto-dumps on a serve-plane fault)
        trace_path = _cli_opt("--trace")
        flight_path = _cli_opt("--flight")
        if trace_path:
            os.environ["CONSENSUS_SPECS_TPU_TRACE"] = "1"
        if flight_path:
            os.environ["CONSENSUS_SPECS_TPU_FLIGHT"] = "1"
        from consensus_specs_tpu.utils.jax_env import force_cpu

        # `--mesh N` shards the service's verify plane over N virtual CPU
        # devices (must be requested BEFORE backend init — XLA reads the
        # host-device-count flag once); the env makes the service's
        # construction-time mesh provider pick it up
        mesh_opt = _cli_opt("--mesh")
        if mesh_opt:
            os.environ["CONSENSUS_SPECS_TPU_MESH"] = mesh_opt
            force_cpu(n_devices=max(1, int(mesh_opt)))
        else:
            force_cpu()
        from consensus_specs_tpu.serve.load import run_serve_bench

        result = run_serve_bench()
        if trace_path:
            from consensus_specs_tpu.obs import tracing

            result["trace"] = tracing.dump_trace(trace_path)
            # monotone count (NOT the ring length): a scaled run traces
            # more requests than the ring retains spans for
            result["trace_requests"] = tracing.global_tracer().finished_total()
        if flight_path:
            from consensus_specs_tpu.obs import flight

            rec = flight.global_recorder()
            result["flight"] = rec.dump(flight_path, reason="bench_flight")
            result["flight_events"] = rec.counters()["events"]
        _emit_result(result)
        return

    if _cli_mode() == "serve-mesh":
        # mesh scaling sweep: one serve-bench child per device count (the
        # virtual-device count is frozen at backend init, so counts can't
        # share a process); the parent does no device work. The `mesh`
        # section is gated round-over-round by tools/bench_compare.py —
        # a device count that verified and now errors fails the round.
        from consensus_specs_tpu.serve.load import run_serve_mesh_sweep

        _emit_result(run_serve_mesh_sweep())
        return

    if _cli_mode() == "serve-fleet":
        # multi-process fleet scaling sweep (ISSUE 11): one FleetRouter
        # per worker count, real worker PROCESSES (each its own GIL/XLA
        # client), aggregate sigs/sec + the merged-scrape exactness
        # property in a `fleet` section gated state-wise by
        # tools/bench_compare.py. The parent imports jax (ops/__init__
        # is eager) but all device work happens in the workers.
        from consensus_specs_tpu.bench.fleet_sweep import run_fleet_bench

        _emit_result(run_fleet_bench())
        return

    if _cli_mode() == "codec":
        # prep-only microbench: batched input codec vs per-item host prep
        # (decode + subgroup + hash-to-G2, no pairings). CPU-forced — the
        # acceptance bar is the codec's host fallback beating the
        # per-item path on plain CPU; CODEC_ITEMS sizes the batch
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.codec_prep import run_codec_bench

        _emit_result(run_codec_bench())
        return

    if _cli_mode() == "head":
        # chain-plane replay: proto-array vs spec-store get_head. CPU-
        # forced — the acceptance bar is the maintained pointer beating
        # the spec recompute >= 10x at the largest tree on plain CPU
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.head_replay import run_head_bench

        _emit_result(run_head_bench())
        return

    if _cli_mode() == "sim":
        # adversarial multi-node simulation: N HeadService nodes over the
        # discrete-event gossip fabric, scenario matrix + convergence
        # gate. CPU-forced — the thing measured is the consensus plane
        # under network faults, not device math
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.sim_matrix import run_sim_bench

        _emit_result(run_sim_bench())
        return

    if _cli_mode() == "proofs":
        # light-client read path (ISSUE 16): per-slot proof artifacts
        # served content-addressed to 10^4+ simulated clients, every one
        # verified (validate_light_client_update + is_valid_merkle_branch
        # against a re-Merkleized root). CPU-forced — the thing measured
        # is proof construction + cache economics, not device math. The
        # `proofs` section is state-gated round over round by
        # tools/bench_compare.py ("PROOFS DIVERGED").
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.proofs import run_proofs_bench

        _emit_result(run_proofs_bench())
        return

    if _cli_mode() == "soak":
        # long-horizon telemetry soak (ISSUE 19): a thousand-plus-slot
        # simnet scenario against the real fleet deployment shape, a
        # per-node health ledger observing every slot, a sim-clock TSDB
        # recording the history, and the stitched cross-process Chrome
        # trace at the end. CPU-forced and crypto-free (verdict-mode
        # workers) — the thing measured is the telemetry plane and
        # fork-choice health over time, not device math. The `health`
        # section is state-gated round over round by
        # tools/bench_compare.py ("HEALTH DIVERGED" when a previously
        # green gate goes red).
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.soak import run_soak_bench

        _emit_result(run_soak_bench())
        return

    if _cli_mode() == "merkle":
        # Merkleization plane race (ISSUE 18): the native batched
        # hash_tree_root path (one sha256_hash_many call per tree level,
        # incremental dirty-set re-roots) vs the pure-python oracle on
        # identical states — full-state cold root, per-block incremental
        # re-root, and the proof-world artifact build. CPU-forced — the
        # thing measured is the host Merkleization plane, not device
        # math. Every cell checks bit-identity; the `merkle` section is
        # state-gated round over round by tools/bench_compare.py
        # ("MERKLE DIVERGED" when a cell's roots stop matching).
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.merkle import run_merkle_bench

        _emit_result(run_merkle_bench())
        return

    if _cli_mode() == "mainnet":
        # mainnet-scale workload replay (ISSUE 20): full mainnet-shape
        # slots over a synthetic million-validator registry —
        # mainnet-preset committee shuffling, hierarchical
        # aggregate-of-aggregates verification folding every committee
        # of a slot into ONE final exp, every key in the device pubkey
        # table gathered by validator index, a forced bad
        # committee localized by bisection, simnet's censored_aggregates
        # at mainnet committee fan-out through the strict convergence
        # gate, and committee-affinity fleet routing. CPU-forced; the
        # `mainnet` section is state-gated round over round by
        # tools/bench_compare.py ("MAINNET DIVERGED" — verdict identity
        # or a gate flipping ok True→False fails the round;
        # attestations/sec is report-only).
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.mainnet import run_mainnet_bench

        _emit_result(run_mainnet_bench())
        return

    if _cli_mode() == "latency":
        # end-to-end gossip→head latency matrix (ISSUE 12): latency_skew
        # and lossy_links simnet scenarios, each under the classic
        # size-or-deadline flush, the slot-budget deadline scheduler, and
        # deadline+speculative head application — gossip_to_head_p99 per
        # scenario with the deadline-flush win quantified. CPU-forced —
        # the thing measured is flush scheduling and fork-choice latency,
        # not device math. The `latency` section is state-gated round
        # over round by tools/bench_compare.py ("LATENCY SLO VIOLATED").
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.latency_pipeline import (
            run_latency_bench,
        )

        _emit_result(run_latency_bench())
        return

    if _cli_mode() == "vmexec":
        # VM execution-backend race (ISSUE 13): the scan interpreter vs
        # the fused straight-line lowering (ops/vm_compile.py) on
        # identical assembled programs, warm ms/row + trace/compile time
        # per (kind, rows) cell, bit-identity checked per cell.
        # CPU-forced; the `vmexec` section is state-gated round over
        # round by tools/bench_compare.py ("VMEXEC ERRORED" — a kind
        # losing its fused backend or the backends disagreeing bitwise
        # fails the round; ms/row movement is report-only). Running this
        # bench also persists each program's measured winner into its
        # .vm_cache plan — the verdict CONSENSUS_SPECS_TPU_VM_EXEC=auto
        # adopts for shapes a warm/pinned call has compiled.
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.vmexec import run_vmexec_bench

        _emit_result(run_vmexec_bench())
        return

    if _cli_mode() == "finalexp":
        # hard-part microbench (ISSUE 10): host-oracle HHT vs the VM
        # hard-part variants (bit_serial, windowed, frobenius) at
        # pipelined rows {1,2,4,8}, plus the vmlint critical-path ratios
        # and the bucketed-vs-legacy assembler race on the chunk-16
        # rlc_combine. CPU-forced; the `finalexp` section is state-gated
        # round over round by tools/bench_compare.py (an errored variant
        # fails the round; a device cell slower than host is report-only)
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.finalexp import run_finalexp_bench

        _emit_result(run_finalexp_bench())
        return

    if _cli_mode() == "rlc":
        # final-exp microbench: per-item easy+hard vs the RLC combine on
        # identical Miller outputs, items/sec across N in {4,16,64,256}.
        # CPU-forced — the acceptance bar is RLC beating the per-item
        # path at N >= 16 on plain CPU; RLC_BENCH_* env sizes it
        from consensus_specs_tpu.utils.jax_env import force_cpu

        force_cpu()
        from consensus_specs_tpu.bench.rlc_final import run_rlc_bench

        _emit_result(run_rlc_bench())
        return

    # default mode: the committee (or epoch) cell in this process, on the
    # device JAX found
    _emit_result(run_workload(emit_partial=_emit_result))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # a parseable error line, no number, failing exit
        import traceback

        tb = traceback.format_exc().strip().splitlines()
        print(json.dumps({"metric": "aggregate BLS signatures verified/sec/chip",
                          "error": f"{type(e).__name__}: {e}",
                          "error_tail": tb[-3:]}), flush=True)
        sys.exit(1)
