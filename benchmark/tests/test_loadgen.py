"""The seeded generator replays byte-identically, emits only the
listed shapes, and gives every seed the same set of sizes."""
import itertools
import json
import os

import pytest

from benchmark.harness import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mixes(kind):
    out = []
    for directory in (TRAFFIC, os.path.join(HERE, "toy")):
        for name in sorted(os.listdir(directory)):
            if name.endswith(".json"):
                with open(os.path.join(directory, name)) as fh:
                    mix = json.load(fh)
                if mix.get("kind") == kind:
                    out.append(pytest.param(mix, id=name))
    return out


def dump(requests):
    return json.dumps([[r.index, r.due_s, r.session_id, r.tokens,
                        r.max_new_tokens] for r in requests])


@pytest.mark.parametrize("mix", mixes("closed"))
def test_closed_replays_and_keeps_to_its_shapes(mix):
    big = 3_000_000_019  # more than 32 signed bits hold
    a = list(itertools.islice(loadgen.closed_requests(mix, 32000, big), 400))
    b = list(itertools.islice(loadgen.closed_requests(mix, 32000, big), 400))
    c = list(itertools.islice(loadgen.closed_requests(mix, 32000, big + 1), 400))
    assert dump(a) == dump(b) != dump(c)
    assert {len(r.tokens) for r in a} <= set(mix["shapes"]["prompt_lens"])
    pool = mix["pool"]
    assert sorted(r.max_new_tokens for r in a[:pool]) == \
        sorted(r.max_new_tokens for r in c[:pool])
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    assert all(lo <= r.max_new_tokens <= hi for r in a)
    assert all(0 < t < 32000 for r in a for t in r.tokens)


@pytest.mark.parametrize("mix", mixes("open"))
def test_open_replays_and_keeps_to_its_shapes(mix):
    a = loadgen.open_schedule(mix, 32000, 7, 30.0)
    b = loadgen.open_schedule(mix, 32000, 7, 30.0)
    c = loadgen.open_schedule(mix, 32000, 8, 30.0)
    assert dump(a) == dump(b) != dump(c)
    # the same schedule of sizes for every seed; the seed makes the ids
    shape = lambda rs: [(r.due_s, r.session_id, len(r.tokens), r.max_new_tokens)  # noqa: E731
                        for r in rs]
    assert shape(a) == shape(c)
    assert len(a) == round(mix["arrivals"]["rate_rps"] * 30.0)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert {len(r.tokens) for r in a} <= set(mix["shapes"]["prompt_lens"])
    if mix.get("sessions"):
        by_session = {}
        for r in a:
            by_session.setdefault(r.session_id, []).append(r)
        for turns in by_session.values():
            for prev, nxt in zip(turns, turns[1:]):
                # a later turn extends the earlier one's prompt
                assert nxt.tokens[:len(prev.tokens)] == prev.tokens
                assert nxt.shared_tokens == len(prev.tokens)
        # every [prefix, suffix] pair the schedule needs is listed to warm
        listed = {tuple(p) for p in mix["shapes"]["extend"]}
        needed = {(r.shared_tokens, len(r.tokens) - r.shared_tokens) for r in a}
        assert needed <= listed, needed - listed


@pytest.mark.parametrize("mix", mixes("train"))
def test_train_tokens_replay(mix):
    a = loadgen.train_tokens(mix, 32000, 3_000_000_019)
    b = loadgen.train_tokens(mix, 32000, 3_000_000_019)
    assert (a == b).all() and a.dtype.name == "int32"
    assert len(a) == mix["windows"] * (mix["seq_len"] + 1)
    assert 0 <= a.min() and a.max() < 32000


def test_batch_rows_match_the_programs_loader(tmp_path):
    """The copied arithmetic against the program's own loader."""
    import numpy as np

    from containerpilot_tpu.workload.data import TokenShardDataset

    mix = {"windows": 64, "seq_len": 16}
    tokens = loadgen.train_tokens(mix, 100, 5)
    np.save(tmp_path / "shard_00000.npy", tokens)
    dataset = TokenShardDataset(str(tmp_path), 16, 2)
    for step in (0, 1, 2, 40, 100):
        rows = loadgen.batch_rows(step, 2, 64)
        want = np.stack([tokens[r * 17:(r + 1) * 17] for r in rows])
        assert (dataset.batch_at(step) == want).all()
