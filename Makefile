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
        detect_generator_incomplete check_vectors multichip clean_vectors \
        generate_random_tests bench-compare check docs sim-smoke mesh-smoke clean \
        finalexp-smoke native sweep fleet-smoke latency-smoke vmexec-smoke \
        proof-smoke merkle-smoke mainnet-smoke

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
	python -m compileall -q consensus_specs_tpu tests __graft_entry__.py
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

# perf regression gate: diff the newest BENCH_r*.json headline against the
# previous round's, keyed by (platform, mode, NxK shape) so CPU fallbacks
# never score against TPU windows; exits nonzero past the allowed drop
# (BENCH_COMPARE_MAX_REGRESSION percent, default 30) — part of `make check`
# so a perf regression is a visible failure, not a silently worse artifact
bench-compare:
	python tools/bench_compare.py

# the static + perf check flow CI runs alongside the test matrix
check: lint vmlint bench-compare sim-smoke

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

# CI convergence canary (part of `make check`): one small 4-node
# partition-and-heal scenario through the STRICT differential gate,
# dumping per-node flight journals to sim_flight/ — uploaded as CI
# artifacts on failure; exits nonzero with the divergence diagnosis
sim-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.sim.smoke

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

# latency-plane CI canary (mirror of sim/mesh/finalexp/fleet smokes): one
# short latency_skew scenario with deadline flushing + speculative head
# application through the STRICT convergence gate, then the
# gossip_to_head_p99 presence assert (the end-to-end histogram must be
# non-empty and the objective met); per-node flight journals land in
# sim_flight/ — uploaded as CI artifacts on failure
latency-smoke:
	JAX_PLATFORMS=cpu python -m consensus_specs_tpu.sim.latency_smoke

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

# sweep the flight journals the smoke targets drop at the repo root (all
# gitignored; this keeps `git status` quiet and the tree reproducible
# after `make sim-smoke` / `mesh-smoke` / `fleet-smoke`)
clean:
	rm -rf serve_flight.jsonl flight_dump.jsonl \
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

