"""Inputs are a function of the seed: the same seed gives the same inputs,
another seed other inputs, and every seed the same amount of work."""
import pytest

from benchmark import generate as gen
from benchmark import judge
from tests.benchmark import tiny

replay = tiny.driver("replay")
gossip = tiny.driver("gossip")


@pytest.fixture(scope="module")
def workers():
    with judge.pool(2) as p:
        yield p


def _blocks(seed, workers):
    cfg = tiny.replay_cell().config
    keys = gen.Keys(seed)
    layout = gen.SlotLayout(cfg, seed)
    keys.derive(list(layout.attesters) + list(layout.sync), workers)
    return replay.blocks(cfg, seed, keys, layout, range(64, 66), {0: 3},
                         workers)


def _flat(blocks):
    return [(c.pubkeys, c.message, c.signature, c.truth)
            for b in blocks for c in b]


def test_same_seed_same_inputs_other_seed_other_inputs(workers):
    a = _flat(_blocks(2**31 + 5, workers))
    b = _flat(_blocks(2**31 + 5, workers))
    c = _flat(_blocks(2**31 + 6, workers))
    assert a == b
    assert len(a) == len(c)
    assert not {x[2] for x in a} & {x[2] for x in c}  # no shared signature
    assert [x[3] for x in a] == [x[3] for x in c]  # same bad position count
    assert sum(not x[3] for x in a) == 1


def test_gossip_work_is_the_same_for_every_seed(workers):
    cell = tiny.gossip_cell()
    sizes = []
    for seed in (2**31 + 1, 2**31 + 2):
        keys = gen.Keys(seed)
        layout = gen.SlotLayout(cell.config, seed)
        keys.derive([i for c in layout.committees for i in c], workers)
        units = gossip.units(cell.config, cell.mix, seed, keys, layout,
                             100, 12, gen.spread(12, 1), workers)
        sizes.append(sorted(len(u.checks[0].members) for u in units))
        assert [not u.checks[0].truth for u in units].index(True) == 6
        assert all(c.truth for u in units for c in u.checks[1:])
    assert sizes[0] == sizes[1]


def test_arrivals_fixed_gaps_inside_the_window():
    a = gen.arrivals(200, 10.0, 20.0, 2**31 + 1)
    b = gen.arrivals(200, 10.0, 20.0, 2**31 + 1)
    c = gen.arrivals(200, 10.0, 20.0, 2**31 + 2)
    assert a == b and a != c
    assert a[0] == 0.0 and a[-1] < 20.0 and c[-1] < 20.0

    def gaps(due):
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:] + [20.0]))

    assert gaps(a) == gaps(c)  # the same set of gaps, in another order
