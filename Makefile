# Build/test orchestration (L8; fills the role of the reference Makefile:90-200).
# No pip installs happen here — everything runs against the baked-in env.

VECTORS_DIR ?= ../consensus-spec-tests/tests
PYTEST = JAX_PLATFORMS=cpu python -m pytest

GENERATORS = operations sanity epoch_processing rewards finality forks transition random \
             fork_choice ssz_static ssz_generic shuffling bls genesis merkle

# sweep split: state-machine-heavy runners emit minimal-preset only (the
# reference's CI posture); cheap runners emit every preset they define —
# shuffling/bls/ssz_generic/merkle cover mainnet/general too. genesis is
# heavy: its mainnet initialization cases build 16k+-validator states
# through per-deposit processing (hours of single-core time, measured)
HEAVY_GENERATORS = operations sanity epoch_processing rewards finality forks transition \
                   random fork_choice ssz_static genesis
CHEAP_GENERATORS = shuffling bls ssz_generic merkle

.PHONY: test citest test_tpu_backend lint vmlint vm-cache-prune generate_tests \
        detect_generator_incomplete check_vectors bench serve-bench codec-bench multichip \
        clean_vectors generate_random_tests bench-compare check serve-trace head-bench docs \
        sim-bench sim-smoke serve-bench-mesh mesh-smoke clean rlc-bench \
        finalexp-bench finalexp-smoke native sweep serve-fleet-bench fleet-smoke \
        latency-bench latency-smoke vmexec-bench vmexec-smoke vmexec-cold-smoke \
        proof-bench proof-smoke merkle-bench merkle-smoke soak-bench soak-smoke \
        mainnet-bench mainnet-smoke

# fast default: BLS stubbed except @always_bls, 4-way process-parallel
# (reference `make test` = pytest -n 4, reference Makefile:100)
test:
	$(PYTEST) tests/ -q -n 4

# CI-grade: everything incl. slow VM/pairing compiles, real BLS via the
# pure-python oracle (reference `make citest` runs milagro)
citest:
	$(PYTEST) tests/ -q -n 4 --run-slow --enable-bls

# the flagship correctness gate: spec tests routed through the TPU backend
test_tpu_backend:
	$(PYTEST) tests/phase0 -q --run-slow --bls-type=tpu

# static gate: compileall (syntax) + speclint (undefined names, unused
# imports, and the built-spec namespace/annotation checks — the role the
# reference fills with flake8 + strict mypy over its generated spec,
# reference Makefile:133-136; neither tool ships in this image)
lint:
	python -m compileall -q consensus_specs_tpu tests bench.py __graft_entry__.py
	JAX_PLATFORMS=cpu python tools/speclint.py

# VM static-analysis gate (tools/vmlint.py over ops/vm_analysis.py): every
# registered field-ALU program gets its magnitude bounds independently
# re-derived and cross-checked against the assembler (carry-safety of the
# 15-limb lanes), its register pressure and live-range-outlier hazards
# checked, and its critical-path/width/cost profile diffed against the
# committed VMLINT_BASELINE.json — a pressure or depth regression fails.
# Re-pin after a conscious program change: python tools/vmlint.py --update-baseline
vmlint:
	JAX_PLATFORMS=cpu python tools/vmlint.py

# bound .vm_cache/ growth: every vmlib/vm/fq edit re-keys all cached
# programs, so stale multi-MB pickles accumulate — evict entries idle
# longer than VM_CACHE_MAX_AGE_DAYS (default 30) and oldest-first past
# VM_CACHE_MAX_BYTES (default 2 GiB)
vm-cache-prune:
	python -c "from consensus_specs_tpu.ops.bls_backend import prune_vm_cache; \
	import json; print(json.dumps(prune_vm_cache()))"

# emit every cross-client vector suite (reference `make generate_tests`)
generate_tests:
	@for g in $(GENERATORS); do \
		JAX_PLATFORMS=cpu python -m consensus_specs_tpu.gen.generators.$$g \
			-o $(VECTORS_DIR) || exit 1; \
	done

# full reproducible sweep + committed evidence: regenerate the tree
# (minimal preset for the heavy state runners, all presets for the cheap
# ones) and write the validated case-count report the repo commits
# (VECTORS_REPORT.md) — `make sweep` is what CI runs and what re-checks
# the round-4 finding that sweep evidence must persist in-repo
sweep:
	@for g in $(HEAVY_GENERATORS); do \
		JAX_PLATFORMS=cpu python -m consensus_specs_tpu.gen.generators.$$g \
			-o $(VECTORS_DIR) -l minimal || exit 1; \
	done
	@for g in $(CHEAP_GENERATORS); do \
		JAX_PLATFORMS=cpu python -m consensus_specs_tpu.gen.generators.$$g \
			-o $(VECTORS_DIR) || exit 1; \
	done
	JAX_PLATFORMS=cpu python tools/check_vectors.py $(VECTORS_DIR) --report VECTORS_REPORT.md

# regenerate the code-generated random scenario-matrix test modules
# (reference `make -C tests/generators/random`)
generate_random_tests:
	python tools/gen_random_tests.py

detect_generator_incomplete:
	python -c "from consensus_specs_tpu.gen.gen_runner import detect_incomplete; \
	import sys; bad = detect_incomplete('$(VECTORS_DIR)'); \
	print('\n'.join(bad) or 'no incomplete cases'); sys.exit(1 if bad else 0)"

# layout + completeness + snappy spot-check of an emitted vector tree
check_vectors:
	JAX_PLATFORMS=cpu python tools/check_vectors.py $(VECTORS_DIR)

bench:
	python bench.py

# perf regression gate: diff the newest BENCH_r*.json headline against the
# previous round's, keyed by (platform, mode, NxK shape) so CPU fallbacks
# never score against TPU windows; exits nonzero past the allowed drop
# (BENCH_COMPARE_MAX_REGRESSION percent, default 30) — part of `make check`
# so a perf regression is a visible failure, not a silently worse artifact
bench-compare:
	python tools/bench_compare.py

# the static + perf check flow CI runs alongside the test matrix
check: lint vmlint bench-compare sim-smoke

# streaming serve plane (consensus_specs_tpu/serve/): short CPU-sized
# synthetic gossip load — Poisson arrivals, duplicate-heavy traffic, one
# injected backend failure — through the continuous-batching
# VerificationService; emits one JSON line with sustained signatures/sec,
# batch occupancy, cache hit rate, p50/p95/p99 submit->result latency,
# and the prep-vs-device time split of the two-stage pipeline
serve-bench:
	JAX_PLATFORMS=cpu python bench.py --mode serve

# serve bench with the full observability plane on: per-request span
# tracing exported as Chrome trace-event JSON (open serve_trace.json in
# chrome://tracing or Perfetto — flight-recorder lane included), the flight recorder's JSONL journal dumped next to it,
# and the /metrics + /snapshot + /healthz (SLO-bearing) + /flightdump
# endpoint live on an ephemeral port during the run. CI uploads
# serve_trace.json as a build artifact.
serve-trace:
	JAX_PLATFORMS=cpu SERVE_METRICS_PORT=0 python bench.py --mode serve --trace serve_trace.json --flight serve_flight.jsonl

# mesh scaling sweep for the serve plane: one serve-bench child per
# device count (SERVE_MESH_DEVICES, default 1,2,4,8 virtual CPU devices;
# the count is frozen at XLA backend init, hence child processes), fault
# injection off. The JSON line's `mesh` section carries per-count
# sigs/sec, mesh fallbacks, and scaling
# efficiency vs single-device (report-only on CPU — two host cores
# timeshare every virtual device; tools/bench_compare.py gates the
# ok-STATE: a device count that verified last round and errors now fails)
serve-bench-mesh:
	JAX_PLATFORMS=cpu python bench.py --mode serve-mesh

# multi-process fleet scaling sweep (ISSUE 11): one FleetRouter fleet of
# real worker PROCESSES per worker count (SERVE_FLEET_WORKERS, default
# 1,2,4 — counts past the 2 physical cores are report-only), each worker
# warmed at exactly the flush shapes its consistent-hash share of the
# stream produces; the JSON line's `fleet` section carries aggregate
# sigs/sec per count plus the merged-scrape exactness property (merged
# /metrics == exact merge of per-worker snapshots: observation counts
# sum, bucket mass sums). tools/bench_compare.py gates the ok-STATE
# ("FLEET ERRORED"); sigs/sec and the 2-worker speedup are report-only.
serve-fleet-bench:
	JAX_PLATFORMS=cpu python bench.py --mode serve-fleet

# fleet control-plane canary (CI, mirror of mesh-smoke): a 2-worker fleet
# through the strict verdict-identity gate (fleet == single-process
# service == host oracle over valid/corrupted/malformed/infinity), then
# one forced worker fault under load must produce an SLO burn-rate-driven
# shed/drain decision reconstructable end-to-end from the merged flight
# journal (decision + worker provenance + ladder transition) and a
# merged-scrape delta; journal dumps to fleet_flight.jsonl (CI artifact
# on failure). Out of tier-1: the workers pay real-backend compiles.
fleet-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.serve.fleet_smoke

# light-client proof plane (ISSUE 16): replay 10^4-10^6 simulated
# read-only clients (CONSENSUS_SPECS_TPU_PROOF_CLIENTS, default 20000)
# against the content-addressed ProofService — R distinct per-slot
# artifacts (finality branch + next-sync-committee branch + assembled
# LightClientUpdate), every one fully verified by the spec's
# validate_light_client_update AND is_valid_merkle_branch against an
# independently re-Merkleized root before the timed window, every served
# request re-checking its finality branch client-side. The JSON line's
# `proofs` section (verified + proofs/sec + cache hit rate + p99) is
# state-gated round over round by tools/bench_compare.py ("PROOFS
# DIVERGED" when a previously-verified shape stops verifying);
# proofs/sec and hit rate are report-only.
# 10^5 clients and a 16k-validator registry since the native
# Merkleization plane (ISSUE 18); override via env
CONSENSUS_SPECS_TPU_PROOF_CLIENTS ?= 100000
proof-bench: native
	JAX_PLATFORMS=cpu \
	CONSENSUS_SPECS_TPU_PROOF_CLIENTS=$(CONSENSUS_SPECS_TPU_PROOF_CLIENTS) \
	python bench.py --mode proofs

# proof-plane CI canary (fleet-smoke's read-path sibling): one full
# artifact served through a ProofService whose sync-committee signature
# verdict routes through a REAL 2-worker fleet, then verified
# client-side via validate_light_client_update + is_valid_merkle_branch
# against an independently re-Merkleized state root (fresh decode_bytes
# round trip — no warm-cache reuse), with a corrupted-branch negative
# control; journal dumps to proof_flight.jsonl (CI artifact on
# failure). Out of tier-1: the workers pay real-backend compiles.
proof-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.lightclient.proof_smoke

# Merkleization plane race (ISSUE 18): the native batched hash_tree_root
# path (csrc sha256_hash_many per tree level + incremental dirty-set
# re-roots) vs the pure-python oracle on identical states — full-state
# cold root, per-block incremental re-root, and the proof-world artifact
# build+sign, each cell checked bit-identical. The JSON line's `merkle`
# section is state-gated round over round by tools/bench_compare.py
# ("MERKLE DIVERGED" when a cell's roots stop matching); speedups and
# roots/sec are report-only. Builds the native kernel first.
merkle-bench: native
	JAX_PLATFORMS=cpu python bench.py --mode merkle

# Merkleization CI canary: native == pure-python oracle BIT-IDENTITY
# over every SSZ shape class (vectors, lists with length mix-ins,
# bitlists, nested containers, zero-subtree padding) plus a seeded
# random incremental-cache invalidation sweep (random dirty sets +
# appends re-rooted against from-scratch rebuilds); journal dumps to
# merkle_flight.jsonl (CI artifact on failure). Crypto-free and
# compile-free — safe anywhere.
merkle-smoke: native
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.merkle.smoke

# mesh convergence canary (CI): one serve flush on a 4-virtual-device
# mesh through the STRICT verdict-identity gate (mesh == single-device ==
# host oracle over valid/corrupted/malformed/infinity inputs, bisection
# through the failed sharded combine included, zero silent fallbacks);
# dumps the flight journal to mesh_flight.jsonl on failure — uploaded as
# a CI artifact. Kept out of tier-1: the sharded compiles cost ~1 min.
mesh-smoke:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
		python -m consensus_specs_tpu.serve.mesh_smoke

# prep-only microbenchmark: the batched input codec (ops/codec.py —
# decompression, subgroup checks, hash-to-G2) vs the per-item pure-Python
# prep path, items/sec on a CPU-sized batch (CODEC_ITEMS, default 64);
# the JSON line's vs_baseline field is the batched-over-per-item speedup
codec-bench:
	JAX_PLATFORMS=cpu python bench.py --mode codec

# chain-plane bench: synthetic fork-and-gossip replay through the
# HeadService + incremental proto-array vs the spec-store get_head
# recompute, at growing block-tree sizes (HEAD_TREE_SIZES env); fault
# injection covers invalid-signature and withheld-block (deferred-then-
# resolved) gossip, and the ephemeral /metrics endpoint is scraped
# mid-replay so the JSON line proves the chain.* gauges answer under load
head-bench:
	JAX_PLATFORMS=cpu SERVE_METRICS_PORT=0 python bench.py --mode head

# adversarial multi-node network simulation (consensus_specs_tpu/sim/):
# every named scenario class — partition/heal, latency skew, lossy links,
# equivocating proposals, withheld-block orphans, long-range reorgs,
# censored aggregates — runs N independent HeadService nodes over the
# deterministic discrete-event gossip fabric; the JSON line reports the
# convergence matrix (every honest head bit-identical to spec.get_head on
# the union view), heal-to-convergence latency, and per-node heads/sec.
# Per-node flight journals land in sim_flight/ (CONSENSUS_SPECS_TPU_SIM_*
# env resizes the run)
sim-bench:
	JAX_PLATFORMS=cpu CONSENSUS_SPECS_TPU_SIM_FLIGHT_DIR=sim_flight python bench.py --mode sim

# CI convergence canary (part of `make check`): one small 4-node
# partition-and-heal scenario through the STRICT differential gate,
# dumping per-node flight journals to sim_flight/ — uploaded as CI
# artifacts on failure; exits nonzero with the divergence diagnosis
sim-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.sim.smoke

# long-horizon telemetry soak (ISSUE 19): a 128-epoch (1000+ slot)
# simnet scenario with periodic partitions, replayed against real
# verdict-mode fleet workers — a per-node chain/health.py ledger
# observes every slot past warm-up, a sim-clock TSDB records the full
# gauge history, and the run ends with the stitched cross-process
# Chrome trace (worker-pid spans joined to router flows by flow id).
# Artifacts land in soak_artifacts/ (timeseries JSONL, stitched trace,
# merged fleet timeseries, HTML/SVG timeline); the `health` section is
# state-gated round over round by tools/bench_compare.py ("HEALTH
# DIVERGED"). CONSENSUS_SPECS_TPU_SOAK_* env resizes.
soak-bench:
	JAX_PLATFORMS=cpu CONSENSUS_SPECS_TPU_SOAK_DIR=soak_artifacts python bench.py --mode soak
	python tools/render_timeline.py soak_artifacts/soak_timeseries.jsonl -o soak_artifacts/soak_timeline.html

# soak CI canary: the same pipeline at 26 epochs (~200 slots, well
# under a minute), with the claims turned into an exit status — health
# gate green, scenario converged, >= 2 worker pids flow-joined in the
# stitched trace, one TSDB sample per slot; the timeline render rides
# along as the uploadable artifact
soak-smoke:
	JAX_PLATFORMS=cpu CONSENSUS_SPECS_TPU_SOAK_DIR=soak_artifacts python -m consensus_specs_tpu.sim.soak_smoke
	python tools/render_timeline.py soak_artifacts/soak_timeseries.jsonl -o soak_artifacts/soak_timeline.html

# mainnet-scale workload replay (ISSUE 20): full mainnet-shape slots over
# the synthetic MILLION-validator registry (scale/) — mainnet-preset
# 64-committee shuffling computed columnar, real index-derived pubkeys,
# per-committee aggregate signatures, the hierarchical aggregate-of-
# aggregates fold (whole slot -> ONE RLC combine -> ONE final exp,
# final_exps_per_slot == 1.0), every key in the device pubkey table
# gathered by validator index, a planted bad committee localized by
# bisection, the strict
# censored_aggregates sim at true 64-committee fan-out, and 2-worker
# committee-affinity fleet routing. The JSON line's `mainnet` section is
# state-gated round over round by tools/bench_compare.py ("MAINNET
# DIVERGED"); attestations/sec, the table's size and peak RSS are
# report-only numbers. CONSENSUS_SPECS_TPU_SCALE_* env resizes.
mainnet-bench:
	JAX_PLATFORMS=cpu python bench.py --mode mainnet

# mainnet-workload CI canary (fleet-smoke's scale sibling): an
# 8192-validator registry (two full-size committees/slot) through the
# valid / censored / planted-bad-committee rounds with hierarchical ==
# flat == host-oracle verdict identity, one-final-exp accounting, keys
# gathered from the pubkey table, and committee affinity stable across a
# real 2-worker verdict fleet; journal dumps to scale_flight.jsonl (CI
# artifact on failure). Crypto-light: summed-sk aggregates over small
# secret keys keep it CI-fast.
mainnet-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.scale.smoke

# end-to-end gossip→head latency matrix (ISSUE 12): latency_skew and
# lossy_links simnet scenarios, each run under the classic
# size-or-deadline flush, the slot-budget deadline scheduler
# (CONSENSUS_SPECS_TPU_SLOT_MS semantics, shared SlotClock), and
# deadline+speculative head application — the JSON line carries
# gossip_to_head p50/p99 per scenario × policy, the deadline-flush win
# (baseline p99 / deadline p99), rollback counts from the invalid-sig
# traffic, and an `slo` section evaluating the declared
# gossip_to_head_p99 objective over the exact merge of the deadline-mode
# histograms. tools/bench_compare.py gates the per-scenario ok-state
# ("LATENCY SLO VIOLATED"); the p99 milliseconds are report-only.
# LATENCY_* env resizes (scenarios, wait, slot, nodes, events).
latency-bench:
	JAX_PLATFORMS=cpu CONSENSUS_SPECS_TPU_SIM_FLIGHT_DIR=sim_flight python bench.py --mode latency

# latency-plane CI canary (mirror of sim/mesh/finalexp/fleet smokes): one
# short latency_skew scenario with deadline flushing + speculative head
# application through the STRICT convergence gate, then the
# gossip_to_head_p99 presence assert (the end-to-end histogram must be
# non-empty and the objective met); per-node flight journals land in
# sim_flight/ — uploaded as CI artifacts on failure
latency-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.sim.latency_smoke

# final-exp microbenchmark: per-item easy+hard finalization vs the RLC
# combine (one final exponentiation per batch) on identical Miller
# outputs, items/sec across N in {4,16,64,256}; the JSON line's
# vs_baseline field is the RLC-over-per-item speedup at N=16 (> 1 means
# the combine wins at the acceptance bar; RLC_BENCH_* env resizes)
rlc-bench:
	JAX_PLATFORMS=cpu python bench.py --mode rlc

# hard-part variant race (ISSUE 10): host-oracle HHT vs the VM variants
# (bit_serial legacy chain, windowed, frobenius) at pipelined rows
# {1,2,4,8} on identical valid unitary inputs, ms/row per cell, plus the
# vmlint critical-path ratios (the >=2.5x depth bar) and the bucketed-vs-
# legacy assembler throughput race on the chunk-16 rlc_combine (the >=4x
# / <=2s bars). `finalexp[variant,rows]` cells are state-gated round over
# round by tools/bench_compare.py — an errored variant fails the round,
# a device route merely slower than host is report-only
finalexp-bench:
	JAX_PLATFORMS=cpu python bench.py --mode finalexp

# VM execution-backend race (ISSUE 13): the scan interpreter vs the fused
# straight-line lowering (ops/vm_compile.py) on identical assembled
# programs — warm ms/row both ways, fused trace/compile seconds, and
# per-cell bit-identity, keyed `vmexec[kind,rows]`. First run on a
# machine pays one XLA compile per (kind, rows) cell (persistent-cached
# after — with ISSUE 15's structural dedup a cell compiles one XLA
# executable per DISTINCT chunk structure, not per chunk);
# VMEXEC_KINDS/VMEXEC_ROWS resize. Cells are state-gated round over
# round by tools/bench_compare.py ("VMEXEC ERRORED" — ms/row is
# report-only). Running it also persists each program's measured winner
# into .vm_cache — the verdict CONSENSUS_SPECS_TPU_VM_EXEC=auto adopts
# (auto serves fused only for shapes a warm/pinned/background-warm call
# has compiled). The cold cells (`cold,<kind>` / `cold_nodedup,<kind>`)
# spawn fresh child processes against fresh XLA caches and race
# structural dedup against the PR 13 per-chunk baseline — the
# `cold_speedup` headline is the ISSUE 15 fresh-process
# time-to-fused-ready win (VMEXEC_COLD=dedup skips the minutes-scale
# baseline arm, VMEXEC_COLD=0 skips both).
vmexec-bench:
	JAX_PLATFORMS=cpu python bench.py --mode vmexec

# fresh-process fused-ready canary (CI, ISSUE 15): one child process
# against a brand-new persistent-XLA-cache dir must reach a fused-ready
# g2_subgroup fold-1 (955-level ladder) with bit-identity — proving a
# fresh CI runner / fleet worker gets the fast path in seconds-scale
# time, not the pre-dedup minutes. The VMEXEC_COLD_BUDGET_S budget
# (default 180 s) is reported here and STATE-gated by bench_compare's
# cold cells, not hard-asserted (slow public runners must not flake CI).
vmexec-cold-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.bench.vmexec_cold --smoke

# execution-backend identity canary (CI, mirror of finalexp-smoke): the
# fused straight-line lowering held to BIT-identity against the scan
# interpreter AND the exact-int IR oracle (vm_analysis.eval_ir) over
# registry programs at small assembly shapes (VMEXEC_SMOKE_FULL=1 runs
# the full production-shape registry), batch axis included; dumps the
# flight journal to vmexec_flight.jsonl on failure — uploaded as a CI
# artifact. Kept out of tier-1: it pays real fused XLA compiles.
vmexec-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.ops.vmexec_smoke

# hard-part bit-identity canary (CI, mirror of mesh-smoke): the windowed
# and Frobenius hard-part programs held to full-coefficient identity
# against the exact-int host oracle over valid AND adversarial Fq12
# inputs (identity, random unitary, conjugates, real valid/corrupted
# verification flows, raw non-unitary feeds under the no-false-accept
# contract); dumps the flight journal to finalexp_flight.jsonl on
# failure — uploaded as a CI artifact. Kept out of tier-1 (three
# hard-part XLA compiles)
finalexp-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.ops.finalexp_smoke

multichip:
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('multichip OK')"

clean_vectors:
	rm -rf $(VECTORS_DIR)

# sweep the bench/observability artifacts the serve/sim/mesh targets drop
# at the repo root (all gitignored; this keeps `git status` quiet and the
# tree reproducible after `make serve-trace` / `sim-bench` / `mesh-smoke`)
clean:
	rm -rf serve_trace.json serve_flight.jsonl flight_dump.jsonl \
		mesh_flight.jsonl finalexp_flight.jsonl sim_flight/ \
		fleet_flight.jsonl serve_flight.*.jsonl flight_dump.*.jsonl \
		mesh_flight.*.jsonl finalexp_flight.*.jsonl fleet_flight.*.jsonl \
		vmexec_flight.jsonl vmexec_flight.*.jsonl \
		proof_flight.jsonl proof_flight.*.jsonl \
		merkle_flight.jsonl merkle_flight.*.jsonl \
		scale_flight.jsonl scale_flight.*.jsonl \
		*-pid[0-9]*.jsonl
	find . -name __pycache__ -type d -prune -exec rm -rf {} +

# build the native kernels (csrc/): batched-SHA256 merkleization, the
# VM assembler's scheduling+allocation kernel (ops/vm.py loads it via
# ctypes when present; the pure-Python bucketed scheduler is the fallback)
# and the host codec's BLS12-381 field arithmetic (utils/native_bls.py;
# the raw-int Python path is the fallback)
native:
	gcc -O3 -fPIC -shared -o csrc/libsha256_batch.so csrc/sha256_batch.c
	gcc -O3 -fPIC -shared -o csrc/libvmsched.so csrc/vm_sched.c
	gcc -O3 -fPIC -shared -o csrc/libbls_host.so csrc/bls_host.c

# regenerate the human-readable per-fork spec document set from specsrc/
docs:
	python tools/render_spec.py
