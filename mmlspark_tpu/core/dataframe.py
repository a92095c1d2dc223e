"""Columnar host-side DataFrame: the data currency of the framework.

Where the reference passes Spark ``DataFrame``s between pipeline stages, this
framework passes a lightweight columnar frame: a dict of numpy arrays (first
axis = rows; trailing axes allowed for tensors such as NHWC images or feature
vectors) plus per-column JSON-able metadata (categorical levels, ML roles —
see :mod:`mmlspark_tpu.core.schema`).

Device placement is explicit and late: stages move the columns they compute
on to TPU as a pytree (``df.device_batch([...])``) and bring results back as
columns. This is the TPU-native replacement for the reference's
``df.mapPartitions { rows => nativeEngine(rows) }`` idiom
(`CNTKModel.scala:497`, `LightGBMBase.scala:65-68`): the per-host columnar
batch is the unit of device work instead of the per-partition row iterator.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Iterable, Iterator, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

ColumnLike = Union[np.ndarray, Sequence[Any]]


def py_scalar(v):
    """Numpy scalar -> plain python (JSON-able, dict-key stable)."""
    return v.item() if isinstance(v, np.generic) else v


def is_null(v) -> bool:
    """None or float NaN (the framework-wide notion of a missing cell)."""
    if v is None:
        return True
    if isinstance(v, (float, np.floating)) and np.isnan(v):
        return True
    return False


def obj_col(items) -> np.ndarray:
    """Sequence -> 1D object array (immune to numpy's 2D inference)."""
    arr = np.empty(len(items), dtype=object)
    for i, v in enumerate(items):
        arr[i] = v
    return arr


def _as_column(values: ColumnLike) -> np.ndarray:
    if isinstance(values, np.ndarray):
        return values
    values = list(values)
    if values and isinstance(values[0], str):
        return np.array(values, dtype=object)
    try:
        arr = np.asarray(values)
        if arr.dtype == np.dtype("O") or arr.dtype.kind in "US":
            return np.array(values, dtype=object)
        return arr
    except (ValueError, TypeError):
        return np.array(values, dtype=object)


class DataFrame:
    """An immutable-ish columnar frame: ordered ``{name: ndarray}`` + metadata."""

    def __init__(self,
                 columns: Mapping[str, ColumnLike],
                 metadata: Optional[Mapping[str, Dict[str, Any]]] = None):
        self._data: Dict[str, np.ndarray] = {}
        n_rows: Optional[int] = None
        for name, values in columns.items():
            col = _as_column(values)
            if n_rows is None:
                n_rows = len(col)
            elif len(col) != n_rows:
                raise ValueError(
                    f"column {name!r} has {len(col)} rows, expected {n_rows}")
            self._data[name] = col
        self._n_rows = n_rows or 0
        self._meta: Dict[str, Dict[str, Any]] = {
            k: dict(v) for k, v in (metadata or {}).items() if k in self._data
        }

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_rows(rows: Sequence[Mapping[str, Any]]) -> "DataFrame":
        if not rows:
            return DataFrame({})
        names = list(rows[0].keys())
        return DataFrame({n: [r[n] for r in rows] for n in names})

    @staticmethod
    def from_pandas(pdf) -> "DataFrame":
        import pandas as pd
        cols = {}
        for name in pdf.columns:
            s = pdf[name]
            if s.dtype == object or str(s.dtype).startswith(("string", "category")):
                cols[str(name)] = np.array(
                    [None if pd.isna(v) else v for v in s.tolist()], dtype=object)
            else:
                cols[str(name)] = s.to_numpy()
        return DataFrame(cols)

    def to_pandas(self):
        import pandas as pd
        out = {}
        for name, col in self._data.items():
            out[name] = list(col) if col.ndim > 1 else col
        return pd.DataFrame(out)

    # -- basic accessors ----------------------------------------------------

    @property
    def columns(self) -> List[str]:
        return list(self._data.keys())

    @property
    def num_rows(self) -> int:
        return self._n_rows

    def __len__(self) -> int:
        return self._n_rows

    def __contains__(self, name: str) -> bool:
        return name in self._data

    def __getitem__(self, name: str) -> np.ndarray:
        if name not in self._data:
            raise KeyError(f"no column {name!r}; have {self.columns}")
        return self._data[name]

    def column(self, name: str) -> np.ndarray:
        return self[name]

    def get_metadata(self, name: str) -> Dict[str, Any]:
        return dict(self._meta.get(name, {}))

    def schema(self) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        return {n: (c.shape[1:], str(c.dtype)) for n, c in self._data.items()}

    def rows(self) -> Iterator[Dict[str, Any]]:
        for i in range(self._n_rows):
            yield {n: c[i] for n, c in self._data.items()}

    def to_dict(self) -> Dict[str, np.ndarray]:
        return dict(self._data)

    # -- transformations (all return new frames) ----------------------------

    def _derive(self, data: Dict[str, np.ndarray],
                meta: Optional[Dict[str, Dict[str, Any]]] = None,
                n_rows: Optional[int] = None) -> "DataFrame":
        out = DataFrame.__new__(DataFrame)
        out._data = data
        if data:
            out._n_rows = len(next(iter(data.values())))
        else:
            out._n_rows = n_rows if n_rows is not None else self._n_rows
        out._meta = meta if meta is not None else {
            k: dict(v) for k, v in self._meta.items() if k in data}
        return out

    def select(self, names: Sequence[str]) -> "DataFrame":
        missing = [n for n in names if n not in self._data]
        if missing:
            raise KeyError(f"no columns {missing}; have {self.columns}")
        return self._derive({n: self._data[n] for n in names})

    def drop(self, *names: str) -> "DataFrame":
        return self._derive({n: c for n, c in self._data.items() if n not in names})

    def with_column(self, name: str, values: ColumnLike,
                    metadata: Optional[Dict[str, Any]] = None) -> "DataFrame":
        col = _as_column(values)
        if (self._data or self._n_rows) and len(col) != self._n_rows:
            raise ValueError(
                f"column {name!r} has {len(col)} rows, expected {self._n_rows}")
        data = dict(self._data)
        data[name] = col
        meta = {k: dict(v) for k, v in self._meta.items() if k in data}
        if metadata is not None:
            meta[name] = dict(metadata)
        elif name in meta:
            meta.pop(name)  # new values invalidate old metadata
        return self._derive(data, meta)

    def with_metadata(self, name: str, metadata: Dict[str, Any]) -> "DataFrame":
        if name not in self._data:
            raise KeyError(name)
        meta = {k: dict(v) for k, v in self._meta.items()}
        meta[name] = dict(metadata)
        return self._derive(dict(self._data), meta)

    def rename(self, mapping: Mapping[str, str]) -> "DataFrame":
        data = {mapping.get(n, n): c for n, c in self._data.items()}
        meta = {mapping.get(n, n): dict(v) for n, v in self._meta.items()}
        return self._derive(data, meta)

    def filter(self, mask: ColumnLike) -> "DataFrame":
        mask = np.asarray(mask, dtype=bool)
        data = {n: c[mask] for n, c in self._data.items()}
        return self._derive(data, n_rows=int(mask.sum()))

    def take(self, indices: ColumnLike) -> "DataFrame":
        idx = np.asarray(indices)
        if idx.size == 0:
            idx = np.zeros(0, dtype=np.int64)
        return self._derive({n: c[idx] for n, c in self._data.items()},
                            n_rows=len(idx))

    def head(self, n: int) -> "DataFrame":
        return self._derive({k: c[:n] for k, c in self._data.items()},
                            n_rows=min(n, self._n_rows))

    def sort_by(self, name: str, ascending: bool = True) -> "DataFrame":
        order = np.argsort(self._data[name], kind="stable")
        if not ascending:
            order = order[::-1]
        return self.take(order)

    def sample(self, fraction: float, seed: int = 0,
               replacement: bool = False) -> "DataFrame":
        rng = np.random.default_rng(seed)
        k = int(round(self._n_rows * fraction))
        idx = rng.choice(self._n_rows, size=k, replace=replacement)
        if not replacement:
            idx = np.sort(idx)
        return self.take(idx)

    def random_split(self, fractions: Sequence[float], seed: int = 0) -> List["DataFrame"]:
        rng = np.random.default_rng(seed)
        perm = rng.permutation(self._n_rows)
        total = float(sum(fractions))
        splits = []
        start = 0
        for i, f in enumerate(fractions):
            end = self._n_rows if i == len(fractions) - 1 else \
                start + int(round(self._n_rows * f / total))
            splits.append(self.take(np.sort(perm[start:end])))
            start = end
        return splits

    def drop_nulls(self, subset: Optional[Sequence[str]] = None) -> "DataFrame":
        """Drop rows with NaN (float cols) or None (object cols).

        When nothing drops (the common serving/featurizer case) the
        frame is returned AS IS: filtering with an all-true mask would
        fancy-index a full copy of every column, and a copied column
        carries a new identity — which silently defeats every
        downstream cache keyed on column identity (NNModel's
        device-resident frame cache re-uploads the whole frame per
        pass)."""
        names = list(subset) if subset is not None else self.columns
        keep = np.ones(self._n_rows, dtype=bool)
        for n in names:
            c = self._data[n]
            if c.dtype == np.dtype("O"):
                keep &= np.array([v is not None for v in c])
            elif np.issubdtype(c.dtype, np.floating):
                # isnan runs natively on every float dtype: casting to
                # float64 first allocated a 2x copy of image-sized
                # columns just to scan them
                flat = c.reshape(len(c), -1) if c.ndim > 1 else c[:, None]
                keep &= ~np.isnan(flat).any(axis=1)
        if keep.all():
            return self
        return self.filter(keep)

    @staticmethod
    def concat(frames: Sequence["DataFrame"]) -> "DataFrame":
        frames = [f for f in frames if f.num_rows > 0 or f.columns]
        if not frames:
            return DataFrame({})
        names = frames[0].columns
        for f in frames[1:]:
            if f.columns != names:
                raise ValueError(f"column mismatch: {f.columns} vs {names}")
        data = {n: np.concatenate([f._data[n] for f in frames]) for n in names}
        meta: Dict[str, Dict[str, Any]] = {}
        for f in frames:  # later frames' metadata wins where present
            for k, v in f._meta.items():
                meta[k] = dict(v)
        return frames[0]._derive(data, meta)

    def map_column(self, name: str, fn: Callable[[Any], Any],
                   output: Optional[str] = None) -> "DataFrame":
        out_name = output or name
        values = [fn(v) for v in self._data[name]]
        return self.with_column(out_name, values)

    # -- batching / device --------------------------------------------------

    def iter_batches(self, batch_size: int,
                     columns: Optional[Sequence[str]] = None) -> Iterator["DataFrame"]:
        names = list(columns) if columns is not None else self.columns
        for start in range(0, self._n_rows, batch_size):
            end = min(start + batch_size, self._n_rows)
            yield self._derive({n: self._data[n][start:end] for n in names})

    def device_batch(self, columns: Sequence[str], dtype=None,
                     sharding=None) -> Dict[str, Any]:
        """Move the named numeric columns to device as a pytree of jax arrays."""
        import jax
        import jax.numpy as jnp
        out = {}
        for n in columns:
            c = self._data[n]
            if c.dtype == np.dtype("O"):
                c = np.stack([np.asarray(v) for v in c])
            arr = jnp.asarray(c, dtype=dtype)
            if sharding is not None:
                arr = jax.device_put(arr, sharding)
            out[n] = arr
        return out

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> None:
        """Persist to ``<path>`` (.npz columns + .meta.json sidecar)."""
        np.savez_compressed(path if path.endswith(".npz") else path + ".npz",
                            **self._data)
        base = path[:-4] if path.endswith(".npz") else path
        from mmlspark_tpu.core.serialize import _json_default
        with open(base + ".meta.json", "w") as f:
            json.dump({"metadata": self._meta, "n_rows": self._n_rows}, f,
                      default=_json_default)

    @staticmethod
    def load(path: str) -> "DataFrame":
        npz_path = path if path.endswith(".npz") else path + ".npz"
        base = path[:-4] if path.endswith(".npz") else path
        with np.load(npz_path, allow_pickle=True) as z:
            data = {k: z[k] for k in z.files}
        meta: Dict[str, Dict[str, Any]] = {}
        n_rows = None
        try:
            with open(base + ".meta.json") as f:
                side = json.load(f)
            meta = side.get("metadata", {})
            n_rows = side.get("n_rows")
        except FileNotFoundError:
            pass
        out = DataFrame(data, metadata=meta)
        if not data and n_rows:
            out._n_rows = n_rows
        return out

    # -- misc ----------------------------------------------------------------

    def __repr__(self) -> str:
        parts = ", ".join(f"{n}:{str(c.dtype)}{list(c.shape[1:]) or ''}"
                          for n, c in self._data.items())
        return f"DataFrame[{self._n_rows} rows; {parts}]"

    def show(self, n: int = 10) -> str:
        lines = ["\t".join(self.columns)]
        for row in self.head(n).rows():
            lines.append("\t".join(str(v) for v in row.values()))
        text = "\n".join(lines)
        print(text)
        return text
