"""Clean twin: acquisitions follow the global order, outermost first."""

from .aff import holds_lock


def _flock(path):
    return open(path)


def claim_then_drain(path):
    with _flock(path):  # rank 0 first...
        return drain()


@holds_lock("partition_claim_flock")
def drain():  # ...then rank 1: ordered
    return 1
