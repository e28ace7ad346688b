"""Seeded LOCK-ORDER inversions: a journal-holding path acquires the
epoch-table flock (rank 0 after rank 2), and a partition-claim-holding
path calls into an epoch-table holder."""

from .aff import holds_lock


def _flock(path):
    return open(path)


@holds_lock("journal_lock")
def flush_entry(path):
    with _flock(path):  # SEEDED VIOLATION: rank-0 lock after rank-2
        return 1


@holds_lock("epoch_table_flock")
def record_claim(rec):
    return rec


@holds_lock("partition_claim_flock")
def drain_and_record():
    return record_claim({})  # SEEDED VIOLATION: callee takes rank 0
