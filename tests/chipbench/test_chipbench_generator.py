"""The traffic generator: every mix is parameters, read by one function."""
import math
from collections import Counter

import pytest
from conftest import ROOT  # noqa: F401  puts the repository on the path

from chipbench import generator


def test_open_loop_seeds_share_the_work_in_another_order():
    mix = {"rate_per_s": 32.0}
    a = generator.arrival_times(mix, 10.0, 2**33 + 1)
    b = generator.arrival_times(mix, 10.0, 7)
    assert len(a) == len(b) == 320 and a != b
    full = [round(-math.log(1.0 - (j + 0.5) / 320) / 32.0, 9) for j in range(320)]
    for t in (a, b):  # every gap of the one set, each once, all but the last
        gaps = Counter(round(y - x, 9) for x, y in zip(t, t[1:]))
        assert not gaps - Counter(full) and sum(gaps.values()) == 319
    assert a[0] == 0.0 and max(a) < 10.0
    assert generator.arrival_times(mix, 10.0, 7) == b


def test_a_group_arrives_at_once():
    t = generator.arrival_times({"rate_per_s": 0.5, "group": 155}, 10.0, 3)
    assert len(t) == 5 * 155
    assert [t.count(x) for x in sorted(set(t))] == [155] * 5


def test_a_closed_loop_mix_has_no_open_loop_arrivals():
    assert generator.arrival_times({"name": "backlog", "keep_queued": 8}, 10.0, 1) == []
    assert generator.checked({"keep_queued": 8})["rate_per_s"] == 0.0


@pytest.mark.parametrize("mix", [{"process": "poisson"}, {"name": "idle"},
                                 {"rate_per_s": 1.0, "group": 0}])
def test_a_mix_that_cannot_be_served_is_refused(mix):
    with pytest.raises(ValueError):
        generator.checked(mix)
