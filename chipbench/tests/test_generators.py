import collections
import json
import os

import numpy as np
import pytest

from chipbench.generators import chat, uniform_ids
from chipbench.harness import loader


def _traffic(name):
    with open(os.path.join(loader.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_bytes_other_seed_other_bytes():
    t = _traffic("chat-batch")
    a = chat.generate(t, 3000000019, 92544)
    b = chat.generate(t, 3000000019, 92544)
    c = chat.generate(t, 3000000020, 92544)
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()


def test_every_seed_gets_the_same_sizes_in_another_order():
    t = _traffic("chat-batch")
    a = chat.generate(t, 1, 92544)
    c = chat.generate(t, 2 ** 31 + 5, 92544)
    sizes = lambda s, f: collections.Counter(f(r) for r in s.requests)
    assert sizes(a, lambda r: len(r.prompt)) == sizes(c, lambda r: len(r.prompt))
    assert sizes(a, lambda r: r.max_new) == sizes(c, lambda r: r.max_new)
    assert [len(r.prompt) for r in a.requests] != \
        [len(r.prompt) for r in c.requests]
    assert sum(r.greedy for r in a.requests) == sum(r.greedy for r in c.requests)


def test_an_arrival_kind_the_generator_does_not_send_is_refused():
    t = dict(_traffic("chat-batch"), arrival={"kind": "poisson",
                                              "rate_rps": 1.5})
    with pytest.raises(ValueError, match="closed loops only"):
        chat.generate(t, 1, 92544)


def test_requests_fit_the_engine_and_share_prefixes():
    t = _traffic("chat-batch")
    s = chat.generate(t, 7, 92544)
    by_tenant = collections.defaultdict(list)
    for r in s.requests:
        assert t["prompt"]["min"] <= len(r.prompt) <= t["prompt"]["max"]
        assert len(r.prompt) + r.max_new <= 2048
        assert r.prompt.dtype == np.int32 and r.prompt.min() >= 3
        by_tenant[r.tenant].append(r)
    assert len(by_tenant) == t["tenants"]
    for reqs in by_tenant.values():
        long = [r for r in reqs if len(r.prompt) >= 272][:3]
        assert all((long[0].prompt[:256] == r.prompt[:256]).all()
                   for r in long)
        assert not (long[0].prompt[256:272] == long[1].prompt[256:272]).all()


def test_training_batches_are_seeded_and_rows_differ():
    t = _traffic("train-4k")
    a = next(uniform_ids.batches(t, 2 ** 31 + 7, 32768))
    b = next(uniform_ids.batches(t, 2 ** 31 + 7, 32768))
    c = next(uniform_ids.batches(t, 2 ** 31 + 8, 32768))
    assert a.shape == (2, 4096) and a.dtype == np.int32
    assert (a == b).all() and not (a == c).all()
    assert not (a[0] == a[1]).all()
    g = uniform_ids.batches(t, 1, 32768)
    assert not (next(g) == next(g)).all()
