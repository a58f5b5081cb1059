"""A traffic file fixes the length sequence; the seed draws only ids."""
import json

import numpy as np

from harness import spec, traffic


def _mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json").read_text())


def test_lengths_do_not_depend_on_the_seed_and_ids_do():
    t = _mix("mixed-backlog")
    lengths = traffic.backlog(t)
    assert traffic.backlog(t) == lengths          # no seed enters at all
    a = traffic.prompt_tokens(7, 3, 100, 64000)
    b = traffic.prompt_tokens(2 ** 33 + 7, 3, 100, 64000)
    c = traffic.prompt_tokens(7, 4, 100, 64000)
    assert len(a) == len(b) == 100
    assert not np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(a, traffic.prompt_tokens(7, 3, 100, 64000))
    assert a.min() >= 1 and a.max() < 64000


def test_mixed_backlog_quantiles():
    t = _mix("mixed-backlog")
    p, o = map(np.asarray, zip(*traffic.backlog(t)))
    assert len(p) == 64
    assert p.min() == 32 and p.max() == 256 and 90 <= np.median(p) <= 110
    assert o.min() == 16 and o.max() == 256 and 56 <= np.median(o) <= 72
    assert (p + o <= t["max_seq"]).all()
    # a fixed order, not sorted
    assert list(p) != sorted(p)


def test_decode_backlog_fits_its_cache():
    t = _mix("decode-backlog")
    lengths = traffic.backlog(t)
    assert lengths == [(512, 512)] * 8
    assert 512 + 512 <= t["max_seq"]
