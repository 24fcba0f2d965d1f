"""tools/closedloop.py: the host-side model of a closed-loop serve cell
that says what the TRAFFIC does to a window's numbers (PERF.md 6, PR 41).
Held here: its order of the requests is `traffic_gen.make_requests`'s, a
prompt's dispatches are the engine's, and the two ways a chunked prompt
can be admitted put what they should into a token gap."""
import json
import os

import pytest

from benchmark import traffic_gen
from tools import closedloop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, 'benchmark', 'traffic',
                       'mixed64-closed.json')) as _f:
    MIXED = json.load(_f)
COSTS = {512: 0.060, 256: 0.048, 128: 0.042}


@pytest.mark.parametrize('seed', [7, 3300000011])
def test_the_order_of_the_requests_is_the_generators(seed):
    small = dict(MIXED, pool_size=128)
    plen, olen = closedloop.lengths(small, seed)
    reqs = traffic_gen.make_requests(small, 50, seed)
    assert [len(r['prompt']) for r in reqs] == list(plen)
    assert [r['max_new_tokens'] for r in reqs] == list(olen)


def test_a_prompts_dispatches_are_the_engines():
    b = [128, 256, 512]
    assert closedloop.chunks(128, b) == [128]
    assert closedloop.chunks(512, b) == [512]
    assert closedloop.chunks(513, b) == [512, 128]
    assert closedloop.chunks(4096, b) == [512] * 8
    assert closedloop.chunks(1300, b) == [512, 512, 512]


@pytest.mark.parametrize('chunk_a_pass', [True, False])
def test_what_a_token_gap_holds(chunk_a_pass):
    """A chunk a pass: the 95th percentile of the gaps is ONE 512 chunk
    and a step whatever the seed. Every chunk at once: it is a whole
    prompt's chunks, two or three as the seed falls. Either way the
    device does the same work, so the tokens a window delivers differ
    by the rows a chunked slot sits out and no more."""
    rows = [closedloop.run(MIXED, seed, 0.016, COSTS, seconds=50.0,
                           chunk_a_pass=chunk_a_pass)
            for seed in (11, 22, 33)]
    for r in rows:
        assert 1500 < r['tokens_per_s'] < 2300
        assert 100 < r['admissions'] < 200
        if chunk_a_pass:
            assert r['itl_p95_ms'] == pytest.approx(76.0, abs=0.01)
        else:
            assert r['itl_p95_ms'] in (
                pytest.approx(2 * 60 + 16, abs=0.01),
                pytest.approx(3 * 60 + 16, abs=0.01),
                pytest.approx(60 + 48 + 16, abs=0.01),
                pytest.approx(60 + 42 + 16, abs=0.01),
                pytest.approx(2 * 60 + 48 + 16, abs=0.01),
                pytest.approx(2 * 60 + 42 + 16, abs=0.01))
    assert closedloop.spread([1.0, 1.01, 1.02, 1.03, 1.04, 2.0]) < 0.03
