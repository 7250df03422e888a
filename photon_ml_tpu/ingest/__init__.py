"""Device-speed ingestion: a staged, threaded pipeline that turns a
directory of Avro shards into a backpressured stream of device-ready
chunks.

The solvers take millions of rows a second a chip (PERF.md section 5)
and a one-shot Avro read does not feed them (ingest rows/s: not measured
on the chip, ROADMAP W5). This package is the subsystem
between the block-parallel decoder (``data/avro_native.py``) and the
device:

- :mod:`.planner` — a file-split planner that assigns sync-delimited Avro
  block ranges to decode workers with DETERMINISTIC chunk ordering
  (stable across runs, so a checkpoint resume replays the same stream
  from the next chunk boundary).
- :mod:`.buffers` — a ring of pre-allocated staging buffers that decode
  workers fill directly in the padded :class:`~photon_ml_tpu.ops.sparse.
  SparseBatch` layout: no per-chunk re-allocation and no COO->padded
  rebuild on the critical path.
- :mod:`.decode` — the per-chunk block-range decoder: the native C++
  interpreter when available, the pure-Python schema walker otherwise
  (identical arrays either way — the pipeline degrades, never crashes).
- :mod:`.pipeline` — :class:`ChunkStream`: decode workers -> deterministic
  reorder -> an async double-buffered uploader that ``device_put``s chunk
  N+1 while chunk N's solve runs, with bounded queues and a typed
  stall/backpressure protocol (:class:`IngestStall`).
- :mod:`.assemble` — :func:`read_game_dataset_streamed`: an out-of-core
  GameDataset build; the host only ever holds the staging ring while the
  feature payload accumulates device-side, bit-identical to the in-core
  reader's arrays.
- :mod:`.prefetch` — :func:`double_buffered`, the generic bounded
  background feeder adopted by ``game/streaming.py`` (its inline feeding
  loop is gone; the trainer is a consumer now).

Telemetry: ``ingest.rows`` / ``ingest.chunks`` / ``ingest.stalls`` /
``ingest.queue_depth`` / ``ingest.solve_waits`` plus per-stage spans, all
surfaced in the heartbeat and the RunReport "Ingestion" section — the
report shows whether the solve ever waited on data.
"""

from photon_ml_tpu.ingest.errors import (  # noqa: F401
    ChunkDecodeError,
    IngestConfigError,
    IngestError,
    IngestStall,
    PipelineClosed,
)
from photon_ml_tpu.ingest.planner import (  # noqa: F401
    ChunkPlan,
    FileMeta,
    plan_chunks,
    plans_for_host,
    read_file_meta,
    scan_blocks,
)
from photon_ml_tpu.ingest.pipeline import (  # noqa: F401
    ChunkStream,
    DeviceChunk,
    IngestSpec,
)
from photon_ml_tpu.ingest.assemble import (  # noqa: F401
    read_game_dataset_streamed,
)
from photon_ml_tpu.ingest.prefetch import double_buffered  # noqa: F401

__all__ = [
    "ChunkDecodeError",
    "ChunkPlan",
    "ChunkStream",
    "DeviceChunk",
    "FileMeta",
    "IngestConfigError",
    "IngestError",
    "IngestSpec",
    "IngestStall",
    "PipelineClosed",
    "double_buffered",
    "plan_chunks",
    "plans_for_host",
    "read_file_meta",
    "read_game_dataset_streamed",
    "scan_blocks",
]
