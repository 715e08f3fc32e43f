"""Chunked, bounded-memory dataset ingestion.

The reference never holds a dataset in RAM: rows stream through Pig/MR
mappers and training datasets spill to disk past a memory envelope
(core/dtrain/dataset/MemoryDiskFloatMLDataSet.java, shifuconfig:46-50).
This module is the TPU-build analog: data is read in fixed-row chunks
(CSV/gzip/Parquet), every stats/norm stage consumes the chunk stream, and
peak host memory is bounded by the chunk size — never the dataset size.

The operational knobs mirror the reference's shifuconfig memory envelope:
    shifu.ingest.chunkRows        rows per chunk (default 65536)
    shifu.ingest.memoryBudgetMB   datasets whose files exceed this budget
                                  switch to the streaming path (default 512)
    shifu.ingest.prefetchChunks   background prefetch depth for the
                                  overlapped pipeline (data/pipeline.py;
                                  default 2, 0 = serial)
"""

from __future__ import annotations

import os
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

try:
    # imported HERE, on the thread that imports this module, not lazily in
    # _string_dtype(): the chunk readers' bodies run on the prefetch worker
    # (data/pipeline.py), and with the installed pyarrow a FIRST import from
    # that non-main thread segfaults intermittently (seen as a crashed
    # `compute_stats_streaming` whenever nothing had imported pandas yet)
    import pyarrow  # noqa: F401

    _HAVE_PYARROW = True
except ImportError:
    _HAVE_PYARROW = False

from shifu_tpu.data.reader import (
    DEFAULT_MISSING,
    ColumnarData,
    _expand_paths,
    drop_stray_header_rows,
)
from shifu_tpu.utils import environment

DEFAULT_CHUNK_ROWS = 65536
DEFAULT_MEMORY_BUDGET_MB = 512

PARQUET_SUFFIXES = (".parquet", ".parq")


def chunk_rows_setting() -> int:
    return environment.get_int("shifu.ingest.chunkRows", DEFAULT_CHUNK_ROWS)


def memory_budget_bytes() -> int:
    mb = environment.get_int("shifu.ingest.memoryBudgetMB",
                             DEFAULT_MEMORY_BUDGET_MB)
    return int(mb) * 1024 * 1024


def dataset_size_bytes(data_path: str) -> int:
    from shifu_tpu.fs.source import size_of

    return sum(size_of(p) for p in _expand_paths(data_path))


def should_stream(data_path: str) -> bool:
    """Stream when the raw files exceed the configured memory budget (the
    in-RAM object representation costs several times the file size)."""
    if environment.get_property("shifu.ingest.forceStreaming", "") in (
        "true", "1",
    ):
        return True
    return dataset_size_bytes(data_path) > memory_budget_bytes()


def _is_parquet(path: str) -> bool:
    return path.endswith(PARQUET_SUFFIXES)


def _string_dtype():
    """Chunk column dtype: pyarrow-backed strings when available (compact
    contiguous buffers — a 500-byte padding field costs 500 bytes, not a
    ~550-byte Python object per row), plain object strings otherwise. The
    LazyColumns facade (data/reader.py) keeps columns in this storage until
    a stage actually reads them, so the bounded-memory envelope holds."""
    return "string[pyarrow]" if _HAVE_PYARROW else str


def _iter_csv_chunks(
    path: str, names: List[str], delimiter: str, chunk_rows: int,
    usecols: Optional[List[str]] = None,
) -> Iterator["np.ndarray"]:
    import pandas as pd

    compression = "gzip" if path.endswith(".gz") else None
    reader = pd.read_csv(
        path,
        sep=delimiter,
        header=None,
        names=names,
        usecols=usecols,
        dtype=_string_dtype(),
        keep_default_na=False,
        compression=compression,
        engine="c",
        skip_blank_lines=True,
        on_bad_lines="skip",
        chunksize=chunk_rows,
    )
    for df in reader:
        yield df


def _iter_parquet_chunks(
    path: str, names: List[str], chunk_rows: int,
    usecols: Optional[List[str]] = None,
) -> Iterator["np.ndarray"]:
    """Parquet ingestion (reference: ModelNormalizeConf.isParquet,
    udf/NormalizeParquetUDF.java) via pyarrow record batches."""
    import pandas as pd
    import pyarrow.parquet as pq

    want = usecols if usecols is not None else names
    pf = pq.ParquetFile(path)
    cols = [c for c in want if c in pf.schema_arrow.names]
    for batch in pf.iter_batches(batch_size=chunk_rows, columns=cols or None):
        df = batch.to_pandas()
        # align to the expected header: missing columns become empty strings
        for c in want:
            if c not in df.columns:
                df[c] = ""
        # nulls must become the empty-string missing token BEFORE astype —
        # astype(str) would stringify them as "nan"/"None" and they'd dodge
        # the missing-value accounting the CSV path gets from
        # keep_default_na=False
        df = df[want].fillna("").astype(_string_dtype())
        yield df


def iter_columnar_chunks(
    data_path: str,
    names: List[str],
    delimiter: str = "|",
    missing_values: Sequence[str] = DEFAULT_MISSING,
    chunk_rows: Optional[int] = None,
    max_rows: Optional[int] = None,
    columns: Optional[Sequence[str]] = None,
) -> Iterator[ColumnarData]:
    """Yield ColumnarData chunks of at most chunk_rows across all part files.

    Pandas frames are converted chunk-by-chunk; nothing beyond one chunk is
    ever resident. `columns`, when given, restricts parsing to that subset
    of the header (pandas usecols): columns a stage never reads — fat meta/
    padding fields — are discarded at tokenizer level and cost no memory at
    all; the yielded chunks carry only the subset (original header order).
    """
    chunk_rows = chunk_rows or chunk_rows_setting()
    usecols = None
    out_names = list(names)
    if columns is not None:
        keep = set(columns)
        out_names = [n for n in names if n in keep]
        usecols = out_names
    remaining = max_rows
    for path in _expand_paths(data_path):
        if _is_parquet(path):
            frames = _iter_parquet_chunks(path, names, chunk_rows, usecols)
        else:
            frames = _iter_csv_chunks(path, names, delimiter, chunk_rows,
                                      usecols)
        for df in frames:
            # filter stray headers BEFORE the max_rows slice so dropped
            # headers don't consume budget
            df = drop_stray_header_rows(df, out_names)
            if remaining is not None:
                if remaining <= 0:
                    return
                df = df.iloc[:remaining]
                remaining -= len(df)
            if not len(df):
                continue
            # frame-backed: columns stay in pandas' compact (arrow) string
            # storage until a stage actually reads them
            yield ColumnarData.from_frame(
                df.reset_index(drop=True), out_names, missing_values
            )


def chunk_source(
    data_path: str,
    names: List[str],
    delimiter: str = "|",
    missing_values: Sequence[str] = DEFAULT_MISSING,
    chunk_rows: Optional[int] = None,
    max_rows: Optional[int] = None,
    columns: Optional[Sequence[str]] = None,
) -> Callable[[], Iterator[ColumnarData]]:
    """A re-iterable chunk factory — multi-pass algorithms (two-pass stats)
    call it once per pass."""

    def factory() -> Iterator[ColumnarData]:
        return iter_columnar_chunks(
            data_path, names, delimiter, missing_values, chunk_rows,
            max_rows, columns,
        )

    return factory
