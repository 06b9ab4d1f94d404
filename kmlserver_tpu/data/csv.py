"""CSV ingestion — the L0→L1 boundary.

The reference reads the playlist-membership CSVs with polars and drops
``duration_ms`` before processing (reference: machine-learning/main.py:148-166,
DROP_COLUMNS at :42). polars is not in this image; ingestion here goes through
pandas' C parser, behind a small facade so the native (C++ mmap) scanner can
slot in underneath later without touching callers.

Expected schema (reference: SURVEY.md §1 L0): ``pid, track_uri, track_name,
artist_name, artist_uri, album_name, duration_ms`` (extra columns tolerated).
Only ``pid`` and ``track_name`` are required; the artist/album columns power
the auxiliary vocab artifacts when present.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pandas as pd

from ..config import DROP_COLUMNS

REQUIRED_COLUMNS = ("pid", "track_name")
OPTIONAL_COLUMNS = ("track_uri", "artist_name", "artist_uri", "album_name")


@dataclasses.dataclass
class TrackTable:
    """Row-oriented membership table: one row per (playlist, track) pair."""

    pid: np.ndarray  # int64
    track_name: np.ndarray  # object (str)
    track_uri: np.ndarray | None = None
    artist_name: np.ndarray | None = None
    artist_uri: np.ndarray | None = None
    album_name: np.ndarray | None = None
    # which parser produced the table ("native" | "pandas"); None for
    # tables built in memory. The job prints it, so a log shows whether
    # the C++ loader was built and used or the pandas path carried the run
    loader: str | None = None

    def __len__(self) -> int:
        return len(self.pid)

    @property
    def n_playlists(self) -> int:
        return len(np.unique(self.pid))

    @property
    def n_tracks(self) -> int:
        return len(np.unique(self.track_name))


def read_tracks(path: str, sample_ratio: float = 1.0) -> TrackTable:
    """Read a membership CSV, optionally head-sampling ``sample_ratio`` of the
    rows, and drop ``duration_ms`` (reference: read_tracks main.py:152-166 +
    clean_df main.py:148-150 — there sampling is also a head-slice, not random).

    Uses the native C++ dictionary-encoding loader (native/kmls_csv.cpp)
    when its .so is available, falling back to pandas' parser.
    """
    from . import native

    if native.available():
        try:
            return _table_from_native(
                native.read_csv_native(path, skip_columns=tuple(DROP_COLUMNS)),
                sample_ratio,
            )
        except ValueError:
            pass  # malformed for the strict native parser → pandas fallback
    # keep_default_na=False: empty cells stay "" exactly as the native path
    # produces them (pandas' default would turn them into NaN → "nan")
    df = pd.read_csv(path, keep_default_na=False)
    missing = [c for c in REQUIRED_COLUMNS if c not in df.columns]
    if missing:
        raise ValueError(f"{path}: missing required columns {missing}; has {list(df.columns)}")
    if 0 < sample_ratio < 1.0:
        df = df.head(max(1, int(len(df) * sample_ratio)))
    df = df.drop(columns=[c for c in DROP_COLUMNS if c in df.columns])
    # same contract as the native parser: non-numeric pids are a parse error,
    # never silently-wrong data (pandas leaves them as an object column)
    try:
        pid_num = pd.to_numeric(df["pid"], errors="raise")
        # reject float-formatted ("1.5", "1.0", "2e3") and out-of-int64-range
        # pids instead of truncating/wrapping them into the wrong playlist —
        # the same strictness the native parser enforces (strtoll + ERANGE
        # treats any non-[0-9] trailing byte as a parse error, so even
        # integral-VALUED float spellings must fail here, not round-trip)
        if pid_num.dtype == np.uint64:
            if (pid_num.to_numpy() > np.uint64(np.iinfo(np.int64).max)).any():
                raise ValueError("pid exceeds int64 range")
        elif not np.issubdtype(pid_num.dtype, np.integer):
            raise ValueError(
                "non-integer-formatted pid value (float spellings like "
                "'1.0' are rejected, matching the native parser)"
            )
        pid = pid_num.astype(np.int64).to_numpy()
    except (ValueError, TypeError) as exc:
        raise ValueError(f"{path}: invalid pid column: {exc}") from None

    def col(name: str) -> np.ndarray | None:
        return df[name].to_numpy() if name in df.columns else None

    return TrackTable(
        pid=pid,
        track_name=df["track_name"].astype(str).to_numpy(),
        track_uri=col("track_uri"),
        artist_name=col("artist_name"),
        artist_uri=col("artist_uri"),
        album_name=col("album_name"),
        loader="pandas",
    )


def _table_from_native(nt, sample_ratio: float) -> TrackTable:
    n = len(nt)
    if "track_name" not in nt.columns:
        raise ValueError("missing required column track_name")
    stop = n
    if 0 < sample_ratio < 1.0:
        stop = max(1, int(n * sample_ratio))

    def col(name: str) -> np.ndarray | None:
        dc = nt.columns.get(name)
        if dc is None:
            return None
        return dc.materialize()[:stop]

    return TrackTable(
        pid=nt.pids[:stop],
        track_name=col("track_name"),
        track_uri=col("track_uri"),
        artist_name=col("artist_name"),
        artist_uri=col("artist_uri"),
        album_name=col("album_name"),
        loader="native",
    )


def write_tracks_csv(path: str, table: TrackTable) -> None:
    """Emit a membership table back to CSV (used by tests and the synthetic
    generator; the reference has no writer — its datasets are inputs only)."""
    data = {"pid": table.pid, "track_name": table.track_name}
    for name in OPTIONAL_COLUMNS:
        arr = getattr(table, name)
        if arr is not None:
            data[name] = arr
    pd.DataFrame(data).to_csv(path, index=False)
