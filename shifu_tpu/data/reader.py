"""Columnar dataset reader.

Replaces the reference's Pig/HDFS ingest (fs/ShifuFileUtils scanners,
udf/AddColumnNumAndFilterUDF row->column scatter): data is read column-wise
into numpy arrays once, then every stage (stats, norm, train, eval) operates
on dense vectors — the layout the TPU actually wants.

A data path may be a single delimited file, a gzip file, or a directory of
part files (part-*, ignoring dot-files), matching the reference's layout.
"""

from __future__ import annotations

import gzip
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from shifu_tpu.config.model_config import DEFAULT_MISSING_VALUES
from shifu_tpu.fs.listing import sorted_glob
from shifu_tpu.utils.errors import ErrorCode, ShifuError

# Default tokens treated as missing (ModelSourceDataConf.missingOrInvalidValues).
DEFAULT_MISSING = tuple(DEFAULT_MISSING_VALUES)


def strip_namespace(name: str) -> str:
    """Reference supports namespaced columns "ns::col" (column/NSColumn.java);
    simple names compare on the last segment."""
    return name.rsplit("::", 1)[-1].strip()


def read_header(header_path: str, delimiter: str = "|") -> List[str]:
    from shifu_tpu.fs.source import is_remote, open_source

    if is_remote(header_path):
        import io

        try:
            raw = open_source(header_path, "rb")
        except (OSError, FileNotFoundError) as e:
            raise ShifuError(ErrorCode.HEADER_NOT_FOUND,
                             f"{header_path} ({e})")
        try:
            fh = (gzip.open(raw, "rt") if header_path.endswith(".gz")
                  else io.TextIOWrapper(raw))
            with fh:
                line = fh.readline().rstrip("\n\r")
        finally:
            raw.close()  # gzip.open(fileobj) does not close the wrapped obj
        names = [strip_namespace(c) for c in line.split(delimiter)]
        return _dedupe_names(names)
    if not os.path.isfile(header_path):
        raise ShifuError(ErrorCode.HEADER_NOT_FOUND, header_path)
    opener = gzip.open if header_path.endswith(".gz") else open
    with opener(header_path, "rt") as fh:
        line = fh.readline().rstrip("\n\r")
    names = [strip_namespace(c) for c in line.split(delimiter)]
    return _dedupe_names(names)


def _dedupe_names(names: List[str]) -> List[str]:
    if len(names) == len(set(names)):
        return names
    # de-duplicate with positional suffixes, as the reference warns+renames
    seen: Dict[str, int] = {}
    out = []
    for n in names:
        if n in seen:
            seen[n] += 1
            out.append(f"{n}_{seen[n]}")
        else:
            seen[n] = 0
            out.append(n)
    return out


def _is_data_file(path: str) -> bool:
    """Skip Hadoop markers (_SUCCESS, _temporary), dot-files, empty files."""
    base = os.path.basename(path)
    if base.startswith(".") or base.startswith("_"):
        return False
    return os.path.isfile(path) and os.path.getsize(path) > 0


def _expand_paths(data_path: str) -> List[str]:
    from shifu_tpu.fs.source import expand_remote, is_remote

    if is_remote(data_path):
        # scheme-ful sources (hdfs://, s3://, gs://, memory://) route
        # through the SourceType seam (fs/source.py); pandas consumes the
        # returned URLs directly
        return expand_remote(data_path)
    if os.path.isdir(data_path):
        parts = [p for p in sorted_glob(os.path.join(data_path, "*"))
                 if _is_data_file(p)]
        if not parts:
            raise ShifuError(ErrorCode.DATA_NOT_FOUND, f"empty directory {data_path}")
        return parts
    if os.path.isfile(data_path):
        return [data_path]
    parts = [p for p in sorted_glob(data_path) if _is_data_file(p)]
    if parts:
        return parts
    raise ShifuError(ErrorCode.DATA_NOT_FOUND, data_path)


def drop_stray_header_rows(df, names: List[str]):
    """Drop stray header lines inside data (part files re-concatenated):
    only rows where EVERY field equals its column name are headers — a
    legitimate row whose first field happens to equal the first column's
    name must survive. Shared by the whole-file and chunked readers so
    both apply the identical rule."""
    if not (len(df) and names):
        return df
    cand = (df[names[0]] == names[0]).to_numpy()
    if not cand.any():
        return df
    sub = df[cand]
    header_row = np.ones(len(sub), dtype=bool)
    for c in names[1:]:
        header_row &= (sub[c] == c).to_numpy()
    if not header_row.any():
        return df
    drop = np.zeros(len(df), dtype=bool)
    drop[np.nonzero(cand)[0][header_row]] = True
    return df[~drop]


class LazyColumns:
    """Mapping facade over a pandas DataFrame that materializes object
    arrays per column ON ACCESS. With pandas' arrow-backed string storage
    this keeps unread columns (fat meta/padding fields) in compact arrow
    buffers — the chunked ingest path's memory depends only on the columns
    a stage actually touches."""

    def __init__(self, frame):
        self._frame = frame
        self._cache: Dict[str, np.ndarray] = {}

    def __getitem__(self, name: str) -> np.ndarray:
        arr = self._cache.get(name)
        if arr is None:
            arr = self._frame[name].to_numpy(dtype=object)
            self._cache[name] = arr
        return arr

    def __contains__(self, name: str) -> bool:
        return name in self._frame.columns

    def __iter__(self):
        return iter(self._frame.columns)

    def __len__(self) -> int:
        return len(self._frame.columns)

    def items(self):
        return ((name, self[name]) for name in self._frame.columns)


def _strings_of_typed(arr: np.ndarray) -> np.ndarray:
    """The canonical string form of a typed numeric column — EXACTLY what
    the JSON path would have carried for the same values (str() of the
    Python scalar; NaN is the "" missing token, JSON null's spelling), so
    a typed column falling back to any string-consuming code path is
    bit-identical to its stringly-typed twin."""
    out = np.empty(len(arr), dtype=object)
    if arr.dtype.kind == "f":
        out[:] = ["" if v != v else str(v) for v in arr.tolist()]
    else:
        out[:] = [str(v) for v in arr.tolist()]
    return out


@dataclass
class ColumnarData:
    """All columns as parallel numpy arrays of raw strings (or a lazy
    frame-backed mapping, or — from the binary wire path — typed numeric
    arrays), plus lazily-parsed numeric views cached per column."""

    names: List[str]
    raw: Dict[str, np.ndarray]
    n_rows: int
    missing_values: Sequence[str] = DEFAULT_MISSING
    _numeric_cache: Dict[str, np.ndarray] = field(default_factory=dict, repr=False)
    _missing_cache: Dict[str, np.ndarray] = field(default_factory=dict, repr=False)
    _string_cache: Dict[str, np.ndarray] = field(default_factory=dict, repr=False)

    @classmethod
    def from_frame(
        cls, frame, names: List[str], missing_values: Sequence[str] = DEFAULT_MISSING
    ) -> "ColumnarData":
        return cls(
            names=list(names),
            raw=LazyColumns(frame),
            n_rows=len(frame),
            missing_values=missing_values,
        )

    def _series(self, name: str):
        """pandas Series view of a column WITHOUT materializing an object
        array (arrow-backed when frame-backed). Typed wire columns enter
        as their canonical strings so every .str consumer keeps working."""
        import pandas as pd

        if isinstance(self.raw, LazyColumns):
            return self.raw._frame[name]
        return pd.Series(self.column(name))

    def typed_column(self, name: str) -> Optional[np.ndarray]:
        """The column's typed numeric array (binary wire batches), else
        None. Consumers that can stay vectorized branch on this; all
        other paths transparently see the canonical strings."""
        if isinstance(self.raw, dict):
            arr = self.raw.get(name)
            if isinstance(arr, np.ndarray) and arr.dtype.kind in "fiu":
                return arr
        return None

    def _typed_fast_ok(self) -> bool:
        """Typed shortcuts (isnan instead of token isin, astype instead
        of to_numeric) are only bit-identical to the string path while no
        missing token itself parses as a number — the same guard
        flat_numeric_matrix applies. "" is exempt: str() of a typed value
        is never empty."""
        return not any(
            _parses_as_number(m) for m in self.missing_values if m != ""
        )

    def column(self, name: str) -> np.ndarray:
        typed = self.typed_column(name)
        if typed is not None:
            cached = self._string_cache.get(name)
            if cached is None:
                cached = _strings_of_typed(typed)
                self._string_cache[name] = cached
            return cached
        return self.raw[name]

    def numeric(self, name: str) -> np.ndarray:
        """float64 view of a column; missing/invalid tokens and non-numeric
        values become NaN."""
        cached = self._numeric_cache.get(name)
        if cached is not None:
            return cached
        typed = self.typed_column(name)
        if typed is not None and self._typed_fast_ok():
            # zero-parse path: the wire already delivered numbers.
            # str(float) round-trips and str(int) parses exactly, so this
            # equals to_numeric over the canonical strings bit-for-bit
            vals = typed.astype(np.float64)
            vals[~np.isfinite(vals)] = np.nan
            self._numeric_cache[name] = vals
            return vals
        import pandas as pd

        ser = self._series(name)
        # copy=True: pandas 3 hands to_numpy() back read-only, and the
        # non-finite pass below writes in place
        vals = pd.to_numeric(ser, errors="coerce").to_numpy(
            dtype=np.float64, copy=True)
        if len(self.missing_values):
            # strip before the missing-set check, exactly like missing_mask —
            # " NA " must count as missing in BOTH views ("" is excluded
            # because to_numeric already coerces blank tokens to NaN)
            miss = ser.str.strip().isin(
                [m for m in self.missing_values if m != ""]
            ).to_numpy()
            vals = np.where(miss, np.nan, vals)
        vals[~np.isfinite(vals)] = np.nan
        self._numeric_cache[name] = vals
        return vals

    def missing_mask(self, name: str) -> np.ndarray:
        """True where the raw token is in the configured missing set.
        Cached — stats touches the same column's mask in several stages
        per chunk, and the prefetch thread warms it for the consumer."""
        cached = self._missing_cache.get(name)
        if cached is not None:
            return cached
        typed = self.typed_column(name)
        if typed is not None and self._typed_fast_ok():
            if typed.dtype.kind == "f" and "" in self.missing_values:
                # NaN's canonical string is "", the missing token; every
                # finite/inf value strings to something numeric, which
                # the guard says is in no missing set
                mask = np.isnan(typed)
                self._missing_cache[name] = mask
                return mask
            if typed.dtype.kind != "f":
                mask = np.zeros(len(typed), dtype=bool)
                self._missing_cache[name] = mask
                return mask
        ser = self._series(name).str.strip()
        mask = ser.isin(list(self.missing_values)).to_numpy()
        self._missing_cache[name] = mask
        return mask

    def select_rows(self, mask: np.ndarray) -> "ColumnarData":
        """Row subset (boolean mask) or reorder (integer index array)."""
        if isinstance(self.raw, LazyColumns):
            mask = np.asarray(mask)
            df = self.raw._frame
            sub = df[mask] if mask.dtype == bool else df.iloc[mask]
            return ColumnarData.from_frame(
                sub.reset_index(drop=True), self.names, self.missing_values
            )
        raw = {k: v[mask] for k, v in self.raw.items()}
        n = len(next(iter(raw.values()))) if raw else 0
        return ColumnarData(
            names=self.names,
            raw=raw,
            n_rows=n,
            missing_values=self.missing_values,
        )

    def sample_rows(self, rate: float, seed: int = 0) -> "ColumnarData":
        if rate >= 1.0:
            return self
        rng = np.random.default_rng(seed)
        mask = rng.random(self.n_rows) < rate
        return self.select_rows(mask)


def read_columnar(
    data_path: str,
    names: List[str],
    delimiter: str = "|",
    missing_values: Sequence[str] = DEFAULT_MISSING,
    max_rows: Optional[int] = None,
) -> ColumnarData:
    """Read a file/dir of delimited rows into string columns via pandas'
    C parser (chunked concat across part files)."""
    import pandas as pd

    frames = []
    remaining = max_rows
    for path in _expand_paths(data_path):
        opener = "gzip" if path.endswith(".gz") else None
        df = pd.read_csv(
            path,
            sep=delimiter,
            header=None,
            names=names,
            dtype=str,
            keep_default_na=False,
            compression=opener,
            engine="c",
            nrows=remaining,
            skip_blank_lines=True,
            on_bad_lines="skip",
        )
        frames.append(df)
        if remaining is not None:
            remaining -= len(df)
            if remaining <= 0:
                break
    df = frames[0] if len(frames) == 1 else pd.concat(frames, ignore_index=True)
    df = drop_stray_header_rows(df, names)
    raw = {name: df[name].to_numpy(dtype=object) for name in names}
    return ColumnarData(
        names=list(names), raw=raw, n_rows=len(df), missing_values=missing_values
    )


def flat_numeric_matrix(data: "ColumnarData",
                        names: Sequence[str]) -> np.ndarray:
    """[n, C] float64 with NaN for missing/invalid — `numeric()`'s exact
    semantics (strip + missing-token set, non-finite -> NaN) over many
    columns in ONE flattened pandas parse. The serve featurizer and the
    drift monitor both bin against this parse; they MUST stay
    bit-identical, which is why there is exactly one implementation.

    Typed columns (binary wire batches) skip the parse entirely — their
    doubles ARE the parse result (same guard as the typed numeric()
    path) — and only the string-backed remainder pays for tokenizing."""
    if data._typed_fast_ok():
        is_typed = [data.typed_column(c) is not None for c in names]
        if any(is_typed):
            out = np.empty((data.n_rows, len(names)), dtype=np.float64)
            rest = [c for j, c in enumerate(names) if not is_typed[j]]
            if rest:
                sub = _flat_parse(data, rest)
                k = 0
                for j, c in enumerate(names):
                    if not is_typed[j]:
                        out[:, j] = sub[:, k]
                        k += 1
            for j, c in enumerate(names):
                if is_typed[j]:
                    out[:, j] = data.numeric(c)
            return out
    return _flat_parse(data, names)


def _flat_parse(data: "ColumnarData", names: Sequence[str]) -> np.ndarray:
    import pandas as pd

    n = data.n_rows
    flat = np.concatenate([
        np.asarray(data.column(c), dtype=object) for c in names
    ])
    tokens = [m for m in data.missing_values if m != ""]
    numeric_tokens = any(_parses_as_number(t) for t in tokens)
    if not numeric_tokens:
        # fast path: a fully numeric batch casts at C speed (~10x the
        # pandas parser — this is the serve hot path, where the parse
        # competes with every replica worker for the GIL). Any
        # missing/invalid value raises and falls back to the coercing
        # parser. Python-float grammar is wider than to_numeric's in
        # exactly two reachable spots — underscore separators ("1_234")
        # and non-ASCII digits ("１２３") parse here but coerce to NaN
        # there — so the vectorized codepoint guard below routes any
        # batch containing either to the slow path; everywhere else
        # the two parsers produce the identical IEEE double (pinned in
        # tests/test_serve.py). Taken only when no missing token itself
        # parses as a number (then the token pass below must see the
        # raw strings).
        try:
            u = flat.astype("U")
            cp = u.view(np.uint32).reshape(len(u), -1)
            if not ((cp == ord("_")).any() or (cp > 127).any()):
                vals = u.astype(np.float64)
                vals[~np.isfinite(vals)] = np.nan
                return vals.reshape(len(names), n).T
        except (TypeError, ValueError):
            pass
    ser = pd.Series(flat)
    # copy=True: pandas 3 hands to_numpy() back read-only, and both
    # passes below write in place
    vals = pd.to_numeric(ser, errors="coerce").to_numpy(np.float64, copy=True)
    if numeric_tokens:
        # the per-element strip+isin pass is a dominant host cost on an
        # online batch, and it can only CHANGE anything when a missing
        # token itself parses as a number (to_numeric already coerced
        # "?"-style tokens to NaN) — so pay it only then; skipping it
        # otherwise is bit-identical
        miss = ser.str.strip().isin(tokens).to_numpy()
        vals[miss] = np.nan
    vals[~np.isfinite(vals)] = np.nan
    return vals.reshape(len(names), n).T


def _parses_as_number(token: str) -> bool:
    """Would pd.to_numeric accept this missing token as a value? (If
    not, the coerce pass already NaN'd every occurrence.)"""
    try:
        float(str(token).strip())
        return True
    except (TypeError, ValueError):
        return False


def make_tags(
    target_col: np.ndarray, pos_tags: Sequence[str], neg_tags: Sequence[str]
) -> np.ndarray:
    """Map raw target values to {1 pos, 0 neg, -1 invalid} (reference filters
    invalid-tag rows out of stats/train)."""
    import pandas as pd

    ser = pd.Series(target_col).str.strip()
    out = np.full(len(target_col), -1, dtype=np.int32)
    out[ser.isin(list(pos_tags)).to_numpy()] = 1
    if neg_tags:
        out[ser.isin(list(neg_tags)).to_numpy()] = 0
    else:
        out[(~ser.isin(list(pos_tags))).to_numpy()] = 0
    return out


def make_class_tags(target_col: np.ndarray, tags: Sequence[str]) -> np.ndarray:
    """Multi-class: map raw target values to their index in the flattened tag
    list (posTags + negTags, one of which is empty in classification mode —
    ModelConfig.getFlattenTags / getSetTags). -1 = invalid, filtered out."""
    import pandas as pd

    ser = pd.Series(target_col).str.strip()
    out = np.full(len(target_col), -1, dtype=np.int32)
    for i, tag in enumerate(tags):
        out[(ser == str(tag).strip()).to_numpy()] = i
    return out


def make_tags_for(mc, target_col: np.ndarray,
                  pos: Optional[Sequence[str]] = None,
                  neg: Optional[Sequence[str]] = None) -> np.ndarray:
    """Dispatch on the ModelConfig's classification mode: regression (binary
    pos+neg) -> {1,0,-1}; multi-class classification -> class index 0..K-1."""
    pos = mc.data_set.pos_tags if pos is None else pos
    neg = mc.data_set.neg_tags if neg is None else neg
    all_tags = list(pos or []) + list(neg or [])
    # classification mode (XOR) uses class indices even for K == 2 — the
    # binary make_tags else-branch would map BOTH listed classes to 1 and
    # junk values to 0
    if bool(pos) != bool(neg) and len(all_tags) >= 2:
        return make_class_tags(target_col, all_tags)
    return make_tags(target_col, pos or [], neg or [])


def make_weights(
    data: ColumnarData, weight_column: Optional[str]
) -> np.ndarray:
    if not weight_column or weight_column not in data.raw:
        return np.ones(data.n_rows, dtype=np.float64)
    w = data.numeric(weight_column)
    w = np.where(np.isfinite(w) & (w >= 0), w, 1.0)
    return w
