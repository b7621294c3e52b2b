"""The traffic generator gives every seed the same work."""
import numpy as np

from bench.lib.harness import load_json
from bench.lib.traffic import Corpus, chat_requests


def test_chat_stream_is_the_same_for_every_seed_and_window():
    """Arrivals, order and lengths are fixed by the mix; a seed changes the
    prompt tokens only, and a longer window extends the same stream."""
    mix = load_json("traffic", "chat")
    rate = 5.2
    streams = [chat_requests(mix, seed, 50277, rate, until)
               for seed, until in ((1, 40.0), (2 ** 31 + 5, 40.0),
                                   (2 ** 40, 100.0))]
    for reqs, until in zip(streams, (40.0, 40.0, 100.0)):
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and due[-1] < until
        assert abs(len(reqs) - rate * until) < 4 * (rate * until) ** 0.5
    a, b, c = streams
    shape = [[(r.due_s, len(r.prompt), r.max_new) for r in s]
             for s in streams]
    assert shape[0] == shape[1] == shape[2][:len(a)]
    assert len(c) > len(a)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert np.array_equal(a[0].prompt, chat_requests(mix, 1, 50277, rate,
                                                     3.0)[0].prompt)
    p, o = mix["prompt"], mix["output"]
    lens = [len(r.prompt) for r in c]
    assert p["min"] <= min(lens) and max(lens) <= p["max"]
    outs = [r.max_new for r in c]
    assert o["min"] <= min(outs) and max(outs) <= o["max"]


def test_corpus_steps_have_the_same_lengths_for_every_seed():
    docs = load_json("traffic", "pack4k")["documents"]
    a, b = Corpus(docs, 1, 50277), Corpus(docs, 2 ** 35, 50277)
    for step in range(3):
        la, lb = a.lengths(step, 50), b.lengths(step, 50)
        assert list(la) == list(lb)
        assert docs["len_min"] <= la.min() and la.max() <= docs["len_max"]
        sa = a.batch_of_sequences(step, 50)
        assert [len(x) for x in sa] == list(la)
        assert a.handed_out[step] is sa
        assert not np.array_equal(sa[0], b.sequence(step, 0, len(sa[0])))
