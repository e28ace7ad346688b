"""Mean host tail of the applier stage's turn in the window, from the
``bench.poll`` and ``bench.drain`` spans of the stage's own trace: the
seconds of both over the count of polls (one poll a turn; a turn whose
poll found nothing drains nothing)."""


def read(layer):
    t = layer.get("trace") or {}
    spans = t.get("spans", {})
    polls, poll_s = spans.get("bench.poll", (0, 0.0))
    _, drain_s = spans.get("bench.drain", (0, 0.0))
    return 1e3 * (poll_s + drain_s) / polls if polls else None
