"""Unified configuration registry.

Ref: the reference scatters configuration across nconf JSON layering per
micro-service (server/routerlicious/config/config.json), ILoaderOptions
threading (container.ts), and static engineering flags
(MergeTree.options); SURVEY §5.6 calls for ONE registry. This module is
it: every tunable the framework reads lives here with its default, and a
config resolves by layering defaults ← explicit overrides ← environment
(``FLUID_TPU_<FIELD>``, the env layer of the nconf pattern).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, fields
from typing import Any, Optional

ENV_PREFIX = "FLUID_TPU_"


@dataclass
class Config:
    """All framework tunables, server and client, in one place."""

    # ---- service: deli sequencer (ref: deli/lambdaFactory.ts:29-37)
    client_timeout_s: float = 300.0      # idle-client eviction
    # ---- service: front door (ref: localDeltaConnectionServer.ts:96)
    max_message_size: int = 16 * 1024    # per-op cap, larger ops nacked
    max_buffered_bytes: int = 32 * 1024 * 1024  # slow-consumer drop bound
    # ---- service: TPU applier geometry (ops/doc_state + tpu_applier)
    applier_max_docs: int = 256          # device doc slots [D]
    applier_max_slots: int = 256         # segment slots per doc [S]
    applier_ops_per_dispatch: int = 32   # wave depth [K]
    applier_overflow_check_every: int = 64  # dispatches between fences
    # the long tier: [L, S_long] device rows beside the [D, S] farm for
    # documents that outgrow S (promoted on the device, not replayed on
    # the host). L is a multiple of 8 (the Pallas tile), S_long a power
    # of two; L = 0 leaves the tier out.
    applier_long_docs: int = 0           # long rows [L]
    applier_long_slots: int = 4096       # segment slots per long row
    # SharedMatrix grids beside the farm: each grid's row and column
    # vectors are two farm rows, its cells a [G, H, H] int32 device store
    # indexed by (row handle, column handle); G = 0 leaves grids out
    applier_grids: int = 0               # grids [G]
    applier_grid_handles: int = 1536     # handles per vector [H]
    # contract-kernel selection for the apply step: "auto" picks the
    # Pallas VMEM-resident kernel on real TPU devices and the XLA scan
    # everywhere else (on a TPU it falls back to XLA, with a warning,
    # when the doc geometry cannot tile, i.e. docs-per-shard % 8 != 0);
    # "pallas"/"xla" force a lane (a forced "pallas" raises on
    # incompatible geometry).
    applier_kernel: str = "auto"
    # ---- client: summarizer heuristics (ref: summarizer.ts:232)
    summary_max_ops: int = 100           # ops since last ack → attempt
    # ---- DDS: merge-tree snapshot chunking (ref: snapshotV1.ts:87)
    summary_chunk_segments: int = 256    # segments per summary chunk blob
    # ---- service: log retention margin kept BELOW an acked summary's
    # capture seq (ops older than that truncate from scriptorium; a
    # client disconnected past the window reloads from the summary).
    # Negative disables truncation entirely.
    log_retention_ops: int = 1000
    # ---- service: GC posture for long-lived service processes
    gc_gen0_threshold: int = 200_000

    def with_overrides(self, **overrides: Any) -> "Config":
        known = {f.name for f in fields(self)}
        bad = set(overrides) - known
        if bad:
            raise KeyError(f"unknown config keys: {sorted(bad)}")
        merged = {f.name: getattr(self, f.name) for f in fields(self)}
        merged.update(overrides)
        return Config(**merged)

    @classmethod
    def from_env(cls, base: Optional["Config"] = None) -> "Config":
        """Environment layer: FLUID_TPU_MAX_MESSAGE_SIZE=65536 etc."""
        base = base or cls()
        overrides: dict[str, Any] = {}
        for f in fields(cls):
            raw = os.environ.get(ENV_PREFIX + f.name.upper())
            if raw is None or raw.strip() == "":
                # set-but-empty (export FLUID_TPU_X=) means "unset" in
                # shell convention: keep the layered default
                continue
            overrides[f.name] = type(getattr(base, f.name))(raw)
        return base.with_overrides(**overrides)


# process-wide default instance (explicit Config args always win)
DEFAULT = Config.from_env()
