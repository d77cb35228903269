"""A later PR adds a configuration, a traffic mix, a kind of loop and a
per-layer metric with new files and entries alone: the harness finds each
by its name, and a new loop runs."""
import json
import os
import shutil
import textwrap

import pytest

from benchmark import cells, run
from tests.benchmark import tiny

SINGLES = textwrap.dedent('''
    """``singles``: a closed loop of batches of single signatures over
    distinct messages, verified through the backend's RLC batch."""
    import time

    from benchmark import generate as gen
    from benchmark.loop import Loop


    class Driver(Loop):
        def setup(self, workers, phase):
            run, b = self.run, int(self.mix["batch"])
            with phase("keys"):
                self.keys = gen.Keys(run.seed)
                self.keys.derive(range(b), workers)
                run.keys_ready(self.keys, workers)
            with phase("bank"):
                pairs = [(self.keys.aggregate_sk([i % b]),
                          gen.root(b"single", run.seed, i))
                         for i in range(int(self.mix["batches"]) * b)]
                sigs = gen.sign_all(workers, pairs)
            checks = [self.keys.check([i % b], msg, sig, True)
                      for i, ((_, msg), sig) in enumerate(zip(pairs, sigs))]
            self.batches = [checks[i:i + b] for i in range(0, len(checks), b)]

        def window(self, tracer):
            t0 = time.perf_counter()
            for batch in self.batches:
                got = self.run.program_backend.batch_verify_rlc(
                    [("fast_aggregate", c.pubkeys, c.message, c.signature)
                     for c in batch])
                self.answers += list(zip(batch, got))
                if time.perf_counter() - t0 >= self.run.seconds:
                    break
            self.window_s = time.perf_counter() - t0
            return {"sigs_per_s": len(self.answers) / self.window_s}

        def sample(self):
            return [c for c, _ in self.answers[:2]]
''')


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark to add files to, and its BENCHMARK.json."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return root, bench


def _write(root, rel, text):
    with open(os.path.join(root, "benchmark", rel), "w") as f:
        f.write(text if isinstance(text, str) else json.dumps(text))


def _commit(root, bench):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_new_config_mix_and_metric_are_found_by_name(tree):
    root, bench = tree
    _write(root, "configs/mainnet-4m.json",
           {"active_validators": 1 << 22, "slots_per_epoch": 32,
            "max_committees_per_slot": 64, "target_committee_size": 128,
            "sync_committee_size": 512})
    _write(root, "traffic/wide_replay.json",
           {"driver": "replay", "max_blocks_per_s": 1.0,
            "bad_checks": [[0, 5]], "warm_bad_checks": [5],
            "reference_blocks": 1, "trace_seconds": 3})
    _write(root, "metrics/combines.block4m.py",
           "def read(ctx):\n    return ctx['window']['combines'] / 2\n")
    bench["configs"].append({"name": "mainnet-4m", "source": "x",
                             "file": "benchmark/configs/mainnet-4m.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "block4m.replay", "config": "mainnet-4m",
                               "traffic": "wide_replay", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("block4m.replay")
    bench["per_layer"].append({"name": "combines.block4m", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "x", "moves": "sigs_per_s",
                               "workloads": ["block4m.replay"]})
    _commit(root, bench)

    cell = cells.load(root, "block4m.replay")
    assert cell.config["active_validators"] == 1 << 22
    assert cell.driver().__module__ == "benchmark_drivers_replay"
    assert [m["name"] for m in cell.end_to_end] == ["sigs_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["combines.block4m"]
    assert cell.reader("combines.block4m")({"window": {"combines": 6}}) == 3
    # the committed cells are unchanged by the additions
    old = cells.load(root, "block300k.replay")
    assert "combines.block4m" not in [m["name"] for m in old.per_layer]


def test_new_driver_file_runs(tree, monkeypatch):
    root, bench = tree
    _write(root, "drivers/singles.py", SINGLES)
    _write(root, "traffic/singles.json",
           {"driver": "singles", "batch": 3, "batches": 4, "trace_seconds": 1})
    bench["workloads"].append({"name": "block300k.singles",
                               "config": "mainnet-300k", "traffic": "singles",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("block300k.singles")
    _commit(root, bench)

    cell = cells.load(root, "block300k.singles")
    truth = tiny.Truth(monkeypatch)
    result, info = run.execute(cell, 2**31 + 9, 5.0, False,
                               backend=tiny.TruthBackend(truth, delay=0.0),
                               require_tpu=False, procs=2)
    assert result["correct"], (result["compared"], info["errors"])
    assert result["attempted"] == 12
    assert set(result["metrics"]) == {"sigs_per_s", "setup_s"}
    assert info["reference_checks"] == 2
