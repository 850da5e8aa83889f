from collections import Counter

import numpy as np

from benchmarks.harness import cells, traffic


def _mix():
    return cells.load_traffic("chat-closed32")


def test_same_seed_same_requests_other_seed_others():
    mix = _mix()
    a = traffic.RequestStream(mix, 32768, 2147483999)
    b = traffic.RequestStream(mix, 32768, 2147483999)
    c = traffic.RequestStream(mix, 32768, 7)
    assert [a.prompt(i) for i in range(40)] == [b.prompt(i)
                                               for i in range(40)]
    assert [a.prompt(i) for i in range(40)] != [c.prompt(i)
                                               for i in range(40)]


def test_every_seed_gets_the_same_lengths_in_another_order():
    mix = _mix()
    a = traffic.RequestStream(mix, 32768, 1)
    b = traffic.RequestStream(mix, 32768, 2)
    n = mix["prompt_tokens"]["distinct_lengths"]
    la = [a.length(i) for i in range(n)]
    lb = [b.length(i) for i in range(n)]
    assert Counter(la) == Counter(lb) and la != lb
    spec = mix["prompt_tokens"]
    assert min(la) >= spec["min"] and max(la) <= spec["max"]
    ordered = sorted(la)
    assert ordered[n // 2 - 1] <= spec["median"] <= ordered[n // 2]
    assert all(len(a.prompt(i)) == a.length(i) for i in range(20))
    assert all(0 < t < 32768 for t in a.prompt(3))


def test_train_rows_follow_the_synthetic_stream():
    rng = np.random.default_rng(5)
    blocks = [rng.integers(0, 512, (4, 65), dtype=np.int32)
              for _ in range(3)]
    batches = traffic.train_batches(5, 3, 4, 64, 512)
    for block, (tokens, targets) in zip(blocks, batches):
        assert (tokens == block[:, :-1]).all()
        assert (targets == block[:, 1:]).all()
