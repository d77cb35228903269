"""Metric-name + env-var drift gate (tier-1).

Every gauge/stat/latency label emitted anywhere in the package must be
declared in the single registry module (``obs/registry.py``) AND appear in
the README metric table; every ``CONSENSUS_SPECS_TPU_*`` environment
variable referenced in the sources must appear in the README env-var
reference. A rename (or a new metric/env knob) that skips the registry or
the docs fails here instead of silently orphaning a dashboard, scrape
rule, or operator playbook.
"""
import os
import re

from consensus_specs_tpu.obs import registry

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_ROOT, "consensus_specs_tpu")

# profiling call sites with a literal first-arg label (multi-line allowed:
# black wraps long calls); labels passed via constants are caught by the
# *_LABEL assignment pattern below
_CALL_RE = re.compile(
    r"profiling\s*\.\s*(?:set_gauge|record_latency|record)\(\s*[\"']([^\"']+)[\"']"
)
# node-labelled emission sites: the base name flows through
# registry.node_label(), which resolves to the bare name or its
# chain[<node>]./serve[<node>]. form — scan the literal first argument
_NODE_LABEL_RE = re.compile(r"node_label\(\s*[\"']([^\"']+)[\"']")
_LABEL_CONST_RE = re.compile(r"^[A-Z_]*LABEL\s*=\s*\"([^\"]+)\"", re.M)
# whole-family declarations (chain/metrics.py GAUGE_LABELS): a tuple of
# label strings exported in a loop — scan every quoted member
_LABEL_TUPLE_RE = re.compile(r"^[A-Z_]*LABELS\s*=\s*\(([^)]*)\)", re.M | re.S)
_ENV_RE = re.compile(r"CONSENSUS_SPECS_TPU_[A-Z0-9_]+")


def _py_sources():
    for dirpath, dirnames, filenames in os.walk(_PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)
    yield os.path.join(_ROOT, "bench.py")


def _emitted_labels():
    labels = {}
    for path in _py_sources():
        with open(path) as fh:
            text = fh.read()
        for m in _CALL_RE.finditer(text):
            labels.setdefault(m.group(1), path)
        for m in _NODE_LABEL_RE.finditer(text):
            labels.setdefault(m.group(1), path)
        for m in _LABEL_CONST_RE.finditer(text):
            labels.setdefault(m.group(1), path)
        for m in _LABEL_TUPLE_RE.finditer(text):
            for member in re.findall(r"\"([^\"]+)\"", m.group(1)):
                labels.setdefault(member, path)
    return labels


def test_every_emitted_label_is_registered():
    missing = {
        label: path
        for label, path in _emitted_labels().items()
        if not registry.known(label)
    }
    assert not missing, (
        "metric labels emitted but missing from obs/registry.py "
        f"(add them to GAUGES/STATS/LATENCIES or DYNAMIC_PREFIXES): {missing}"
    )


def test_emitted_labels_were_actually_found():
    # the scan itself must keep working: the serve plane's known labels
    # have to show up, else a refactor broke the regexes, not the metrics
    found = _emitted_labels()
    for expected in ("serve.queue_depth", "serve.submit_to_result",
                     "bls.rlc_combines", "bls.vm_cache_hits",
                     "chain.apply_batch", "chain.head_changes",
                     "chain.reorgs", "chain.dropped_attestations",
                     "vm.analysis_programs", "vm.analysis_errors",
                     "vm.analysis_hazards", "vm.analysis_max_live",
                     "hist.families", "flight.dropped", "flight.events",
                     "slo.ok", "bls.vm_cache_pruned_bytes",
                     "scale.final_exps_per_slot", "scale.pubkey_hit_rate"):
        assert expected in found, f"label scan lost {expected}"


def test_vm_gauge_families_are_complete():
    # every vm.* gauge either exporter emits (vm.analysis_* from
    # ops/vm_analysis.export_to_obs, vm.fused_* from
    # ops/vm_compile._export_gauges) must be registered, and every
    # registered vm.* gauge must have an emission site — a renamed
    # metric can never silently orphan the README table or a scrape rule
    emitted = {label for label in _emitted_labels()
               if label.startswith("vm.")}
    registered = {n for n in registry.GAUGES if n.startswith("vm.")}
    assert emitted == registered, (
        f"vm gauge drift: emitted-not-registered="
        f"{emitted - registered}, registered-not-emitted="
        f"{registered - emitted}"
    )


def test_merkle_gauge_family_is_complete():
    # the Merkleization plane (ISSUE 18): every merkle.* gauge
    # merkle/levels.export_gauges emits must be registered and every
    # registered merkle.* gauge must have an emission site, and the
    # family must track the counters dict one-to-one (a new counter
    # that skips export_gauges never reaches a scrape)
    from consensus_specs_tpu.merkle import levels as merkle_levels

    emitted = {label for label in _emitted_labels()
               if label.startswith("merkle.")}
    registered = {n for n in registry.GAUGES if n.startswith("merkle.")}
    assert emitted == registered, (
        f"merkle gauge drift: emitted-not-registered="
        f"{emitted - registered}, registered-not-emitted="
        f"{registered - emitted}"
    )
    assert {f"merkle.{k}" for k in merkle_levels.counters} == registered, (
        "merkle counters dict and registered merkle.* gauges diverged"
    )


def test_scale_gauge_family_is_complete():
    # the mainnet workload plane (ISSUE 20): every scale.* gauge the
    # registry / pubkey plane / hierarchy fold / fleet routing emit must
    # be registered and every registered scale.* gauge must have an
    # emission site — the million-validator replay's numbers (pubkey hit
    # rate, final exps per slot, affinity moves) can never silently
    # orphan the README table or a scrape rule
    emitted = {label for label in _emitted_labels()
               if label.startswith("scale.")}
    registered = {n for n in registry.GAUGES if n.startswith("scale.")}
    assert registered, "the scale.* gauge family vanished from the registry"
    assert emitted == registered, (
        f"scale gauge drift: emitted-not-registered="
        f"{emitted - registered}, registered-not-emitted="
        f"{registered - emitted}"
    )


def test_chain_gauge_family_is_complete():
    # the chain plane exports its whole gauge family from one tuple; every
    # member must be a registered gauge and every registered chain gauge
    # must be in the tuple (else export_gauges silently skips it)
    from consensus_specs_tpu.chain import metrics as chain_metrics

    declared = set(chain_metrics.GAUGE_LABELS)
    registered = {n for n in registry.GAUGES if n.startswith("chain.")}
    assert declared == registered, (
        f"chain gauge drift: declared-not-registered={declared - registered}, "
        f"registered-not-declared={registered - declared}"
    )


def test_fleet_gauge_families_are_complete():
    # the fleet families (mergeable histograms, flight recorder, SLO
    # tracker): every emitted static label is registered AND every
    # registered label has an emission site — a rename in either
    # direction fails here instead of orphaning a scrape rule
    emitted = _emitted_labels()
    for prefix in ("hist.", "flight.", "slo.", "fleet."):
        family_emitted = {l for l in emitted if l.startswith(prefix)}
        family_registered = {n for n in registry.GAUGES
                             if n.startswith(prefix)}
        assert family_emitted == family_registered, (
            f"{prefix}* gauge drift: emitted-not-registered="
            f"{family_emitted - family_registered}, "
            f"registered-not-emitted={family_registered - family_emitted}"
        )
    # the occupancy ledger's families went with it (the profiler trace
    # and the flush records replaced it): nothing emits or registers them
    assert not os.path.exists(os.path.join(_PKG, "obs", "devices.py"))
    assert "device[" not in registry.DYNAMIC_PREFIXES
    assert not {l for l in set(emitted) | set(registry.GAUGES)
                if l.startswith("device.")}


def test_node_labelled_families_registered():
    # the simnet multi-instance forms: chain[<node>].<name> and
    # serve[<node>].<name> are registered dynamic families, resolve
    # through known(), and spell exactly what node_label() emits —
    # N HeadService/VerificationService instances in one process must
    # publish side by side, never collide
    assert "chain[" in registry.DYNAMIC_PREFIXES
    assert "serve[" in registry.DYNAMIC_PREFIXES
    for label in ("chain[n0].head_slot", "chain[n3].apply_batch",
                  "serve[n0].queue_depth", "serve[n1].submit_to_result"):
        assert registry.known(label), f"{label} not resolvable"
    # node_label is the one spelling, and both planes route through it
    assert registry.node_label("chain.head_slot", "n2") == \
        "chain[n2].head_slot"
    assert registry.node_label("serve.queue_depth", None) == \
        "serve.queue_depth"
    for rel in (("chain", "metrics.py"), ("serve", "metrics.py")):
        src = open(os.path.join(_PKG, *rel)).read()
        assert "node_label(" in src, f"{rel} lost its node_label route"


def test_node_labelled_bases_cover_the_bare_families():
    # every label a node-labelled instance can emit must be a registered
    # BARE name too (the node form only re-scopes it): the scan sees the
    # node_label("<base>") literals, and each base must be registered
    emitted = _emitted_labels()
    node_routed = set()
    for rel in (("chain", "metrics.py"), ("serve", "metrics.py")):
        src = open(os.path.join(_PKG, *rel)).read()
        node_routed.update(_NODE_LABEL_RE.findall(src))
        node_routed.update(_LABEL_CONST_RE.findall(src))
    assert node_routed, "node_label scan found no emission sites"
    for base in node_routed:
        assert registry.known(base), f"node-labelled base {base} unregistered"
        assert base in emitted


def test_telemetry_gauge_families_are_complete():
    # the continuous-telemetry plane (ISSUE 19): the health.* family must
    # track chain/health.GAUGE_LABELS one-to-one (export_gauges zips the
    # tuple — a gauge outside it silently never exports), the TSDB's own
    # timeseries.* health and the snapshot's process.* resource family
    # must each match emitted-vs-registered exactly
    from consensus_specs_tpu.chain import health as chain_health
    from consensus_specs_tpu.obs import snapshot as obs_snapshot

    emitted = _emitted_labels()
    for prefix in ("health.", "timeseries.", "process."):
        family_emitted = {l for l in emitted if l.startswith(prefix)}
        family_registered = {n for n in registry.GAUGES
                             if n.startswith(prefix)}
        assert family_emitted == family_registered, (
            f"{prefix}* gauge drift: emitted-not-registered="
            f"{family_emitted - family_registered}, "
            f"registered-not-emitted={family_registered - family_emitted}"
        )
    assert set(chain_health.GAUGE_LABELS) == \
        {n for n in registry.GAUGES if n.startswith("health.")}, \
        "chain/health.GAUGE_LABELS and registered health.* diverged"
    assert set(obs_snapshot.PROCESS_GAUGE_LABELS) == \
        {n for n in registry.GAUGES if n.startswith("process.")}, \
        "snapshot.PROCESS_GAUGE_LABELS and registered process.* diverged"


def test_telemetry_node_labelled_families_registered():
    # the per-instance forms (health[<node>].<name> from N simnet
    # ledgers, process[<worker>].<name> from the fleet merge) are
    # registered dynamic families and resolve through known()
    assert "health[" in registry.DYNAMIC_PREFIXES
    assert "process[" in registry.DYNAMIC_PREFIXES
    for label in ("health[n0].participation_rate",
                  "health[n3].finality_lag_slots",
                  "process[w0].rss_bytes", "process[w1].cpu_s"):
        assert registry.known(label), f"{label} not resolvable"
    assert registry.node_label("health.head_churn", "n1") == \
        "health[n1].head_churn"
    src = open(os.path.join(_PKG, "chain", "health.py")).read()
    assert "node_label(" in src, "health.py lost its node_label route"


def test_span_stage_registry_matches_tracing_exports():
    # obs/registry.SPAN_STAGES is the canonical stage list; tracing
    # re-exports it — the coverage gate in tests/test_obs.py holds every
    # registered stage to an actual trace export
    from consensus_specs_tpu.obs import tracing

    assert tracing.STAGES == registry.SPAN_STAGES["serve"]
    assert tracing.CHAIN_STAGES == registry.SPAN_STAGES["chain"]


def test_registry_names_are_documented():
    with open(os.path.join(_ROOT, "README.md")) as fh:
        readme = fh.read()
    undocumented = [n for n in registry.all_names() if f"`{n}`" not in readme]
    assert not undocumented, (
        "registered metric names missing from the README metric table: "
        f"{undocumented}"
    )
    for prefix in registry.DYNAMIC_PREFIXES:
        assert f"`{prefix}" in readme, (
            f"dynamic metric family {prefix!r} missing from the README "
            "metric table"
        )


def test_dynamic_prefixes_exist_in_source():
    # a registered dynamic family must correspond to a real emission site
    vm_src = open(os.path.join(_PKG, "ops", "vm.py")).read()
    assert 'f"vm[steps=' in vm_src


def test_env_vars_are_documented():
    with open(os.path.join(_ROOT, "README.md")) as fh:
        readme = fh.read()
    referenced = set()
    for path in _py_sources():
        with open(path) as fh:
            referenced.update(_ENV_RE.findall(fh.read()))
    undocumented = sorted(v for v in referenced if v not in readme)
    assert not undocumented, (
        "CONSENSUS_SPECS_TPU_* env vars referenced in sources but missing "
        f"from the README env-var reference: {undocumented}"
    )
