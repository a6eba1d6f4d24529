"""The generators: the same seed gives the same inputs, every seed the
same set of sizes in another order, large seeds are taken."""

import numpy as np

from benchmark.harness import cells
from benchmark.traffic_kinds import serve_closed, train_steps

BIG = 2**31 + 12345


def test_train_batches_repeat_and_differ():
    cell = cells.Cell("bert-base-train-s384")
    a = train_steps.batch_for(cell.traffic, cell.config, BIG, 3)
    b = train_steps.batch_for(cell.traffic, cell.config, BIG, 3)
    c = train_steps.batch_for(cell.traffic, cell.config, BIG, 4)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["src_ids"] == c["src_ids"]).all()
    assert a["src_ids"].shape == (32, 384) and a["label"].shape == (32,)
    assert a["src_ids"].max() < cell.config["vocab_size"]
    assert a["label"].sum() == 24 and set(a["label"]) == {0, 1}
    assert (a["label"] != c["label"]).any()
    # two segments: zeros then ones, both present in every row
    sent = a["sent_ids"]
    assert (np.diff(sent, axis=1) >= 0).all()
    assert sent[:, 0].max() == 0 and sent[:, -1].min() == 1
    # rows all differ
    assert len({r.tobytes() for r in a["src_ids"]}) == 32


def test_serve_plan_same_sizes_every_seed():
    cell = cells.Cell("gpt2s-serve-chat")
    sizes = serve_closed.size_set(cell.traffic)
    assert len(sizes) == 64
    assert min(p for p, _ in sizes) >= 32 and max(p for p, _ in sizes) <= 512
    assert min(o for _, o in sizes) >= 32 and max(o for _, o in sizes) <= 96

    def first_cycle(seed):
        plan = serve_closed.Plan(cell.traffic, 50257, seed)
        return [plan.take() for _ in range(64)]

    one, two, again = first_cycle(1), first_cycle(BIG), first_cycle(1)
    assert sorted((len(p), o) for _i, p, o in one) == sorted(sizes)
    # the same sizes in the same order for every seed, other token ids
    assert [(len(p), o) for _i, p, o in one] == \
        [(len(p), o) for _i, p, o in two]
    assert [p for _i, p, _o in one] != [p for _i, p, _o in two]
    assert [p for _i, p, _o in one] == [p for _i, p, _o in again]
    assert 1 <= serve_closed.Plan(cell.traffic, 50257, 1).first_cut(5, 32) \
        <= 32


def test_tokens_in_window_does_not_move_with_the_edges():
    """64 streams stepping in one tick: whole tokens counted at arrival
    move by a tick's worth with the window's phase, the shares do not."""
    period, streams = 0.41, 64

    def records(first_tick):
        out = []
        for c in range(streams):
            r = serve_closed.Record(c, c, [1], 400)
            r.sent = first_tick - period
            r.times = [first_tick + period * k for k in range(400)]
            out.append(r)
        return out

    served, arrived = [], []
    for phase in (0.0, 0.1, 0.2, 0.3, 0.4):
        recs = records(1.0 + phase)
        t0, t1 = 10.0, 50.0
        served.append(serve_closed.tokens_in_window(recs, t0, t1))
        arrived.append(sum(t0 <= x <= t1 for r in recs for x in r.times))
    assert max(arrived) - min(arrived) == streams
    assert max(served) - min(served) < 1e-6
    assert abs(served[0] - streams * 40.0 / period) < 1e-6
    # a request's first token is made from its POST on; one that began
    # before the window counts by the part inside
    r = serve_closed.Record(0, 0, [1], 2)
    r.sent, r.times = 9.0, [11.0, 11.0]
    assert serve_closed.tokens_in_window([r], 10.0, 50.0) == 0.5 + 1.0
