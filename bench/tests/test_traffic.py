"""The traffic generator: deterministic in the seed, the same work for every
seed, and arrivals inside the window."""
import numpy as np

from bench import traffic

MIX_OPEN = {"loop": "open", "arrival": "poisson",
            "prompt": {"median": 768, "sigma": 0.35, "min": 256, "max": 1024},
            "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 32}}
MIX_CLOSED = {"loop": "closed", "backlog_per_slot": 2,
              "prompt": {"median": 1024, "sigma": 0.5, "min": 256, "max": 2048},
              "output": {"median": 24, "sigma": 0.5, "min": 8, "max": 64}}


def _key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


def test_open_loop_is_deterministic_in_the_seed():
    big = 2 ** 40 + 12345            # seeds wider than 32 bits
    a = traffic.open_loop(MIX_OPEN, 4.0, 30.0, big, 49155)
    b = traffic.open_loop(MIX_OPEN, 4.0, 30.0, big, 49155)
    c = traffic.open_loop(MIX_OPEN, 4.0, 30.0, big + 2 ** 33, 49155)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_replays_the_same_schedule_with_its_own_tokens():
    a = traffic.open_loop(MIX_OPEN, 4.0, 30.0, 1, 49155)
    b = traffic.open_loop(MIX_OPEN, 4.0, 30.0, 2, 49155)
    assert len(a) == len(b) == 120
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert a[0].prompt.tobytes() != b[0].prompt.tobytes()
    gaps = np.diff([r.due_s for r in a])
    assert abs(len(a) / (gaps.sum() + gaps.mean()) - 4.0) < 0.5


def test_open_loop_arrivals_lie_in_the_window_and_lengths_in_range():
    reqs = traffic.open_loop(MIX_OPEN, 4.0, 30.0, 3, 49155)
    due = [r.due_s for r in reqs]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0
    lens = np.array([len(r.prompt) for r in reqs])
    assert lens.min() >= 256 and lens.max() <= 1024
    assert 700 <= np.median(lens) <= 840
    assert all(0 <= t < 49155 for r in reqs for t in r.prompt[:8])


def test_closed_loop_blocks_repeat_the_same_lengths():
    a = traffic.closed_loop_block(MIX_CLOSED, 0, 9, 49152)
    b = traffic.closed_loop_block(MIX_CLOSED, 1, 9, 49152)
    assert len(a) == traffic.CLOSED_BLOCK
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert _key(a) == _key(traffic.closed_loop_block(MIX_CLOSED, 0, 9, 49152))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    c = traffic.closed_loop_block(MIX_CLOSED, 0, 10, 49152)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert _key(a) != _key(c)
