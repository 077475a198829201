from types import SimpleNamespace

from harness import check


def _done(lengths):
    return [SimpleNamespace(rid=i, served=[0] * n)
            for i, n in enumerate(lengths)]


def test_the_sample_is_a_count_of_requests_with_the_longest_first():
    done = _done([5, 900, 40, 7, 300, 12, 60, 8, 20, 3])
    s = check.sample_requests(done, 3_000_000_001, 8)
    assert len(s) == 8 and len({r.rid for r in s}) == 8
    assert s[0].rid == 1                  # 900 served tokens
    # the long one does not stop the draw: seven more come after it
    assert sum(len(r.served) for r in s[1:]) > 0


def test_the_sample_repeats_per_seed_and_takes_all_when_few_finished():
    done = _done(range(1, 30))
    a = [r.rid for r in check.sample_requests(done, 7, 12)]
    assert a == [r.rid for r in check.sample_requests(done, 7, 12)]
    assert a != [r.rid for r in check.sample_requests(done, 8, 12)]
    few = _done([4, 9, 2])
    assert sorted(r.rid for r in check.sample_requests(few, 7, 12)) == \
        [0, 1, 2]
    assert check.sample_requests([], 7, 12) == []
