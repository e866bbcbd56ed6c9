"""Binned dataset construction for lightgbm_tpu.

TPU-native re-design of the reference's ``Dataset`` / ``DatasetLoader`` /
``Metadata`` (reference: include/LightGBM/dataset.h:48,487,
src/io/dataset_loader.cpp — ``ConstructFromSampleData`` dataset_loader.cpp:593,
src/io/metadata.cpp).

Differences from the reference, by TPU design:
  * no FeatureGroup / EFB / sparse bins — the binned matrix is a single dense
    ``[N, F]`` uint8/uint16 array living in HBM, padded to a common per-feature
    bin count ``max_num_bins`` (dense layout is what the histogram matmul wants;
    EFB's memory win matters much less when bins are 1 byte and HBM is tens of GB);
  * construction is vectorized numpy on host, then one device_put.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..utils import log
from .binning import (
    MISSING_NAN,
    BinMapper,
    find_bin_categorical,
    find_bin_numerical,
)


def _to_2d_float(data: Any) -> np.ndarray:
    """Coerce input features to a float64 2-D numpy array (host side).

    scipy CSR/CSC matrices densify here: the TPU bin storage is a dense
    [N, F] uint8 matrix by design (io/dataset.py module doc — HBM-friendly
    MXU layout), so sparse inputs are a host-side ingestion format, not a
    device format (reference accepts CSR/CSC the same way through
    LGBM_DatasetCreateFromCSR/CSC, src/c_api.cpp)."""
    if hasattr(data, "tocsr") and hasattr(data, "toarray"):  # scipy.sparse
        arr = data.toarray()
    elif type(data).__module__.startswith("pyarrow"):
        # Arrow Table/RecordBatch ingestion (reference:
        # LGBM_DatasetCreateFromArrow, include/LightGBM/arrow.h)
        arr = np.column_stack([
            np.asarray(data.column(i)) for i in range(data.num_columns)])
    elif hasattr(data, "values") and hasattr(data, "columns"):  # pandas
        arr = data.values
    else:
        arr = data
    arr = np.asarray(arr)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        raise ValueError(f"features must be 2-D, got shape {arr.shape}")
    if arr.dtype == np.float32:
        # keep float32: promoting a 10M x 4228 matrix to float64 doubles
        # peak host memory for nothing — every bound comparison in the
        # binning path upcasts exactly, so bins are bit-identical
        # (io/binning.py bin_columns)
        return arr
    return arr.astype(np.float64, copy=False)


def _feature_names_of(data: Any, num_features: int) -> List[str]:
    if hasattr(data, "column_names"):  # pyarrow Table / RecordBatch
        return [str(c) for c in data.column_names]
    if hasattr(data, "columns"):
        return [str(c) for c in data.columns]
    return [f"Column_{i}" for i in range(num_features)]


class Metadata:
    """Label / weight / query-group / init_score container
    (reference: Metadata, include/LightGBM/dataset.h:48)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None
        self.group: Optional[np.ndarray] = None          # per-group sizes
        self.query_boundaries: Optional[np.ndarray] = None  # cumulative [num_groups+1]
        self.position: Optional[np.ndarray] = None

    def set_label(self, label: Any) -> None:
        arr = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            raise ValueError(f"label length {len(arr)} != num_data {self.num_data}")
        self.label = arr

    def set_weight(self, weight: Any) -> None:
        if weight is None:
            self.weight = None
            return
        arr = np.asarray(weight, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            raise ValueError(f"weight length {len(arr)} != num_data {self.num_data}")
        self.weight = arr

    def set_init_score(self, init_score: Any) -> None:
        if init_score is None:
            self.init_score = None
            return
        arr = np.asarray(init_score, dtype=np.float64)
        self.init_score = arr

    def set_group(self, group: Any) -> None:
        if group is None:
            self.group = None
            self.query_boundaries = None
            return
        arr = np.asarray(group, dtype=np.int64).reshape(-1)
        if arr.sum() != self.num_data:
            raise ValueError(
                f"sum of group sizes ({arr.sum()}) != num_data ({self.num_data})"
            )
        self.group = arr
        self.query_boundaries = np.concatenate([[0], np.cumsum(arr)]).astype(np.int64)

    def set_position(self, position: Any) -> None:
        if position is None:
            self.position = None
            return
        self.position = np.asarray(position, dtype=np.int64).reshape(-1)

    @property
    def num_queries(self) -> int:
        return 0 if self.group is None else len(self.group)


class BinnedDataset:
    """The constructed (binned) training dataset.

    reference analogue: ``Dataset`` (include/LightGBM/dataset.h:487). Holds the
    dense binned matrix, per-feature BinMappers, and Metadata.
    """

    def __init__(self):
        # [N, n_columns] uint8/uint16; n_columns == F unless EFB bundled
        self.binned: Optional[np.ndarray] = None
        self.bundle_info = None                    # io/efb.py BundleInfo
        self.mappers: List[BinMapper] = []
        self.feature_names: List[str] = []
        self.metadata: Optional[Metadata] = None
        self.max_num_bins: int = 1                 # B: common padded bin count
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.used_features: List[int] = []         # non-trivial feature indices
        self.categorical_features: List[int] = []
        self.raw_data: Optional[np.ndarray] = None  # kept only if needed (linear trees)
        # cached reference bin-occupancy (serving drift monitors); built
        # lazily so construct pays nothing when drift is off
        self._ref_dist: Optional[tuple] = None

    # -- binary serialization (reference: Dataset::SaveBinaryFile,
    # src/io/dataset.cpp / DatasetLoader::LoadFromBinFile :417) -------------
    def save_binary(self, path: str) -> None:
        """Save the constructed dataset (bins + mappers + metadata) so later
        runs skip text parsing and re-binning.

        Mappers serialize as JSON inside the npz (never pickle: loading a
        dataset file must not execute code — the reference's binary format is
        plain structs, dataset_loader.cpp:417)."""
        import json
        mapper_blobs = [{
            "num_bins": int(m.num_bins),
            "is_categorical": bool(m.is_categorical),
            "missing_type": int(m.missing_type),
            # non-finite bounds (the last bound is always +inf) go as strings
            # so the blob stays strict RFC-8259 JSON for external consumers
            "bin_upper_bounds": [float(x) if np.isfinite(x) else str(float(x))
                                 for x in m.bin_upper_bounds],
            "cat_to_bin": {str(k): int(v) for k, v in m.cat_to_bin.items()},
            "bin_to_cat": [int(x) for x in m.bin_to_cat],
            "default_bin": int(m.default_bin),
            # same finite-check encoding as bin_upper_bounds: +/-inf feature
            # values flow into min/max and would make json.dumps raise
            "min_value": (float(m.min_value) if np.isfinite(m.min_value)
                          else str(float(m.min_value))),
            "max_value": (float(m.max_value) if np.isfinite(m.max_value)
                          else str(float(m.max_value))),
        } for m in self.mappers]
        md = self.metadata
        # np.savez appends '.npz' to bare paths; write via a handle so the
        # requested filename (e.g. train.bin) is used verbatim
        fh = open(path, "wb")
        np.savez_compressed(
            fh,
            magic=np.frombuffer(b"lgbtpu.bin.v2\x00\x00\x00", np.uint8),
            binned=self.binned,
            feature_names=np.asarray(self.feature_names),
            max_num_bins=self.max_num_bins,
            num_data=self.num_data,
            num_total_features=self.num_total_features,
            used_features=np.asarray(self.used_features, np.int64),
            categorical_features=np.asarray(self.categorical_features,
                                            np.int64),
            bundle_col_of=(np.asarray(self.bundle_info.col_of, np.int64)
                           if self.bundle_info is not None
                           else np.zeros(0, np.int64)),
            bundle_offset_of=(np.asarray(self.bundle_info.offset_of, np.int64)
                              if self.bundle_info is not None
                              else np.zeros(0, np.int64)),
            bundle_col_bins=(np.asarray(self.bundle_info.num_column_bins,
                                        np.int64)
                             if self.bundle_info is not None
                             else np.zeros(0, np.int64)),
            mappers=np.frombuffer(
                json.dumps(mapper_blobs, allow_nan=False).encode(), np.uint8),
            label=md.label if md.label is not None else np.zeros(0),
            weight=md.weight if md.weight is not None else np.zeros(0),
            init_score=(md.init_score if md.init_score is not None
                        else np.zeros(0)),
            group=md.group if md.group is not None else np.zeros(0, np.int64),
            position=(md.position if md.position is not None
                      else np.zeros(0)),
        )
        fh.close()

    @staticmethod
    def load_binary(path: str) -> "BinnedDataset":
        import json
        from .binning import BinMapper
        z = np.load(path, allow_pickle=False)
        if bytes(z["magic"].tobytes())[:13] != b"lgbtpu.bin.v2":
            raise ValueError(
                f"{path} is not a lightgbm_tpu binary dataset (v2); "
                "re-save with save_binary()")
        ds = BinnedDataset()
        ds.binned = z["binned"]
        ds.feature_names = [str(x) for x in z["feature_names"]]
        ds.max_num_bins = int(z["max_num_bins"])
        ds.num_data = int(z["num_data"])
        ds.num_total_features = int(z["num_total_features"])
        ds.used_features = [int(i) for i in z["used_features"]]
        ds.categorical_features = [int(i) for i in z["categorical_features"]]
        if "bundle_col_of" in z and z["bundle_col_of"].size:
            from .efb import BundleInfo
            col_of = z["bundle_col_of"].astype(np.int32)
            off_of = z["bundle_offset_of"].astype(np.int32)
            ds.bundle_info = BundleInfo(
                col_of=col_of, offset_of=off_of,
                num_column_bins=z["bundle_col_bins"].astype(np.int32),
                n_columns=int(z["bundle_col_bins"].size),
                n_bundled=int((off_of >= 0).sum()))
        blobs = json.loads(z["mappers"].tobytes().decode())
        for blob in blobs:
            blob["bin_upper_bounds"] = np.asarray(
                [float(v) for v in blob["bin_upper_bounds"]], np.float64)
            blob["cat_to_bin"] = {int(k): int(v)
                                  for k, v in blob["cat_to_bin"].items()}
            blob["bin_to_cat"] = np.asarray(blob["bin_to_cat"], np.int64)
            blob["min_value"] = float(blob["min_value"])
            blob["max_value"] = float(blob["max_value"])
        ds.mappers = [BinMapper(**blob) for blob in blobs]
        md = Metadata(ds.num_data)
        for name in ("label", "weight", "init_score", "position"):
            arr = z[name]
            if arr.size:
                setattr(md, name, arr)
        if z["group"].size:
            md.set_group(z["group"])
        ds.metadata = md
        return ds

    # -- construction -------------------------------------------------------
    @staticmethod
    def construct(
        data: Any,
        *,
        max_bin: int = 255,
        min_data_in_bin: int = 3,
        bin_construct_sample_cnt: int = 200000,
        use_missing: bool = True,
        zero_as_missing: bool = False,
        categorical_feature: Optional[Sequence[Union[int, str]]] = None,
        feature_names: Optional[Sequence[str]] = None,
        data_random_seed: int = 1,
        reference: Optional["BinnedDataset"] = None,
        keep_raw: bool = False,
        forcedbins_filename: str = "",
        max_bin_by_feature: Optional[Sequence[int]] = None,
        enable_bundle: bool = True,
        max_conflict_rate: float = 1e-4,
    ) -> "BinnedDataset":
        arr = _to_2d_float(data)
        n, f = arr.shape
        ds = BinnedDataset()
        ds.num_data = n
        ds.num_total_features = f
        ds.feature_names = (
            list(feature_names) if feature_names is not None else _feature_names_of(data, f)
        )
        if len(ds.feature_names) != f:
            raise ValueError("feature_names length mismatch")

        if reference is not None:
            # valid set: reuse the reference's bin mappers
            # (reference: Dataset::CreateValid, dataset.h:703)
            if f != reference.num_total_features:
                raise ValueError(
                    f"validation data has {f} features, training data had "
                    f"{reference.num_total_features}"
                )
            ds.mappers = reference.mappers
            ds.max_num_bins = reference.max_num_bins
            ds.used_features = reference.used_features
            ds.categorical_features = reference.categorical_features
        else:
            cat_idx = _resolve_categorical(categorical_feature, ds.feature_names)
            ds.categorical_features = sorted(cat_idx)
            from ..obs.spans import span
            with span("find_bins"):
                # sample rows for bin construction (reference:
                # bin_construct_sample_cnt)
                if n > bin_construct_sample_cnt:
                    rng = np.random.RandomState(data_random_seed)
                    sample_idx = rng.choice(
                        n, size=bin_construct_sample_cnt, replace=False)
                    sample = arr[np.sort(sample_idx)]
                else:
                    sample = arr
                # multi-host: every process contributes its sample and all
                # build identical mappers from the pooled global
                # distribution (reference: ConstructBinMappersFromTextData,
                # src/io/dataset_loader.cpp:1070)
                from ..parallel.multihost import pool_bin_sample
                sample = pool_bin_sample(sample)
                _fit_mappers(ds, sample, f, cat_idx, max_bin,
                             min_data_in_bin, use_missing, zero_as_missing,
                             forcedbins_filename, max_bin_by_feature)

        # bin all columns — batched over row chunks and column groups
        # (io/binning.py bin_columns, the construct hot path)
        dtype = np.uint8 if ds.max_num_bins <= 256 else np.uint16
        from .binning import bin_columns
        binned = bin_columns(ds.mappers, arr, dtype)
        # Exclusive Feature Bundling: pack mutually-exclusive sparse features
        # into shared columns (reference: FeatureGroup / Dataset::Construct
        # FindGroups, include/LightGBM/feature_group.h). The growers then see
        # n_columns ( << F on one-hot-wide data) storage columns.
        if reference is not None:
            info = reference.bundle_info
            if info is not None:
                binned = _apply_bundles(binned, info, ds, max_conflict_rate)
        elif enable_bundle and ds.max_num_bins <= 256:
            srows = min(n, 50_000)
            info = _plan_efb(ds, binned[:srows], max_bin, max_conflict_rate)
            if info is not None:
                ds.bundle_info = info
                binned = _apply_bundles(binned, info, ds, max_conflict_rate)
                log.info(
                    f"EFB: bundled {info.n_bundled} of {f} features into "
                    f"{info.n_columns} stored columns")
        ds.binned = binned
        ds.metadata = Metadata(n)
        if keep_raw:
            # linear-tree least squares runs on raw values; keep those in
            # float64 regardless of the float32 binning fast path
            ds.raw_data = arr.astype(np.float64, copy=False)
        return ds

    @staticmethod
    def construct_from_sequences(
        seqs: List[Any],
        *,
        max_bin: int = 255,
        min_data_in_bin: int = 3,
        bin_construct_sample_cnt: int = 200000,
        use_missing: bool = True,
        zero_as_missing: bool = False,
        categorical_feature: Optional[Sequence[Union[int, str]]] = None,
        feature_names: Optional[Sequence[str]] = None,
        data_random_seed: int = 1,
        reference: Optional["BinnedDataset"] = None,
        forcedbins_filename: str = "",
        max_bin_by_feature: Optional[Sequence[int]] = None,
        enable_bundle: bool = True,
        max_conflict_rate: float = 1e-4,
    ) -> "BinnedDataset":
        """Streaming construction from Sequence objects (random row access
        + batched range reads): the raw [N, F] float matrix is NEVER
        materialized — peak host memory is the packed bin matrix plus one
        batch (reference: Sequence-based construction,
        python-package/lightgbm/basic.py Sequence +
        Dataset::PushOneRow/FinishLoad, include/LightGBM/dataset.h:583)."""
        lens = [len(s) for s in seqs]
        n = int(sum(lens))
        if n == 0:
            raise ValueError("empty Sequence data")
        probe = next(s for s, m in zip(seqs, lens) if m > 0)
        first = np.asarray(probe[0], np.float64).reshape(-1)
        f = first.shape[0]
        ds = BinnedDataset()
        ds.num_data = n
        ds.num_total_features = f
        ds.feature_names = (list(feature_names) if feature_names is not None
                            else [f"Column_{j}" for j in range(f)])
        if len(ds.feature_names) != f:
            raise ValueError("feature_names length mismatch")

        offsets = np.cumsum([0] + lens)
        if reference is not None:
            if f != reference.num_total_features:
                raise ValueError(
                    f"validation data has {f} features, training data had "
                    f"{reference.num_total_features}")
            ds.mappers = reference.mappers
            ds.max_num_bins = reference.max_num_bins
            ds.used_features = reference.used_features
            ds.categorical_features = reference.categorical_features
            info = reference.bundle_info
        else:
            cat_idx = _resolve_categorical(categorical_feature,
                                           ds.feature_names)
            ds.categorical_features = sorted(cat_idx)
            s_cnt = min(n, bin_construct_sample_cnt)
            rng = np.random.RandomState(data_random_seed)
            idx = np.sort(rng.choice(n, size=s_cnt, replace=False)) \
                if s_cnt < n else np.arange(n)
            sample = np.empty((s_cnt, f), np.float64)
            si = np.searchsorted(offsets, idx, side="right") - 1
            pos = 0
            for sq_i, sq in enumerate(seqs):
                local = (idx[si == sq_i] - offsets[sq_i]).astype(np.int64)
                if not len(local):
                    continue
                m = len(sq)
                if len(local) * 3 >= m:
                    # dense sample: batched slice reads + subset (one
                    # storage round trip per batch, not per row)
                    bs0 = int(getattr(sq, "batch_size", 4096) or 4096)
                    for a in range(0, m, bs0):
                        sel = local[(local >= a) & (local < a + bs0)]
                        if not len(sel):
                            continue
                        batch = np.asarray(sq[a:min(a + bs0, m)],
                                           np.float64).reshape(-1, f)
                        take = batch[sel - a]
                        sample[pos:pos + len(take)] = take
                        pos += len(take)
                else:
                    for i in local:
                        sample[pos] = np.asarray(
                            sq[int(i)], np.float64).reshape(-1)
                        pos += 1
            from ..parallel.multihost import pool_bin_sample
            sample = pool_bin_sample(sample)
            _fit_mappers(ds, sample, f, cat_idx, max_bin, min_data_in_bin,
                         use_missing, zero_as_missing, forcedbins_filename,
                         max_bin_by_feature)
            info = None
            if enable_bundle and ds.max_num_bins <= 256:
                # cap the planning sample like the in-memory path: the
                # planner's occupancy matrix scales with sample rows
                sb = _bin_chunk(ds.mappers, sample[:50_000], np.uint8)
                info = _plan_efb(ds, sb, max_bin, max_conflict_rate)

        dtype = np.uint8 if ds.max_num_bins <= 256 else np.uint16
        dbins_all = np.array([m.default_bin for m in ds.mappers], np.int32)

        def stream(binfo):
            from .efb import bundle_chunk
            cols = binfo.n_columns if binfo is not None else f
            out = np.zeros((n, cols), dtype)
            conflicts = 0
            pos = 0
            for sq in seqs:
                bs = int(getattr(sq, "batch_size", 4096) or 4096)
                m = len(sq)
                for a in range(0, m, bs):
                    raw = np.asarray(sq[a:min(a + bs, m)], np.float64)
                    if raw.ndim == 1:
                        raw = raw.reshape(1, -1)
                    if raw.shape[1] != f:
                        raise ValueError(
                            f"Sequence batch has {raw.shape[1]} features, "
                            f"expected {f}")
                    if raw.shape[0] != min(a + bs, m) - a:
                        raise ValueError(
                            "Sequence slice returned "
                            f"{raw.shape[0]} rows for a "
                            f"{min(a + bs, m) - a}-row range")
                    chunk = _bin_chunk(ds.mappers, raw, dtype)
                    k = chunk.shape[0]
                    if binfo is not None:
                        enc, cf = bundle_chunk(chunk, binfo, dbins_all)
                        conflicts += cf
                        out[pos:pos + k] = enc
                    else:
                        out[pos:pos + k] = chunk
                    pos += k
            if pos != n:
                raise ValueError(
                    f"Sequences yielded {pos} rows, __len__ promised {n}")
            return out, conflicts

        out, conflicts = stream(info)
        if info is not None and reference is None:
            from .efb import conflict_allowance
            if conflicts > conflict_allowance(info, n, max_conflict_rate):
                log.warning("EFB: feature conflict outside the planning "
                            "sample; keeping the dense matrix")
                info = None
                out, _ = stream(None)
            else:
                log.info(
                    f"EFB: bundled {info.n_bundled} of {f} features into "
                    f"{info.n_columns} stored columns (streaming)")
        ds.bundle_info = info
        ds.binned = out
        ds.metadata = Metadata(n)
        return ds

    # -- views for the tree learner ----------------------------------------
    def reference_bin_distribution(self):
        """Normalized per-ORIGINAL-feature bin occupancy of this
        dataset's rows: ``(probs [F, B] float32, num_bins [F] int32)``.

        The drift monitor's reference (ISSUE 14): live serving traffic
        is binned in original feature space with these exact mappers, so
        the per-feature occupancy of the training data is the
        distribution a served window's occupancy is compared against
        (PSI/KL). Computed from the stored bin matrix — EFB bundle
        columns decode through their reserved offset ranges
        (io/binning.bin_occupancy) — and cached: the registry
        materializes it during the deploy warm phase so the monitor
        ships WITH the model and the swap flip never stalls on a data
        pass."""
        if self._ref_dist is not None:
            return self._ref_dist
        if self.binned is None:
            raise ValueError("dataset is not constructed")
        from .binning import bin_occupancy
        counts, nb = bin_occupancy(self.binned, self.mappers,
                                   self.bundle_info)
        probs = (counts / np.maximum(counts.sum(axis=1, keepdims=True), 1)
                 ).astype(np.float32)
        self._ref_dist = (probs, nb)
        return self._ref_dist

    @property
    def num_features(self) -> int:
        return self.num_total_features

    def feature_num_bins(self) -> np.ndarray:
        return np.array([m.num_bins for m in self.mappers], dtype=np.int32)

    def feature_nan_bins(self) -> np.ndarray:
        """Per feature: the bin NaN maps to (for default-direction handling)."""
        return np.array(
            [m.nan_bin if not m.is_trivial else 0 for m in self.mappers],
            dtype=np.int32,
        )

    def feature_is_categorical(self) -> np.ndarray:
        return np.array([m.is_categorical for m in self.mappers], dtype=bool)


def _plan_efb(ds, sample_binned, max_bin, max_conflict_rate):
    """Plan Exclusive Feature Bundling from a binned sample; returns
    BundleInfo or None (shared by the in-memory and streaming paths)."""
    from .efb import build_bundle_info, plan_bundles
    dbins = np.array([m.default_bin for m in ds.mappers], np.int32)
    nbins = np.array([m.num_bins for m in ds.mappers], np.int32)
    ok = np.array(
        [(not m.is_categorical) and m.missing_type != MISSING_NAN
         and not m.is_trivial for m in ds.mappers], bool)
    bundles = plan_bundles(sample_binned, nbins, dbins, ok, max_bin=max_bin,
                           max_conflict_rate=max_conflict_rate)
    if not bundles:
        return None
    return build_bundle_info(bundles, nbins, ds.num_total_features)


def _bin_chunk(mappers, arr: np.ndarray, dtype) -> np.ndarray:
    """Bin a raw [K, F] float chunk with fitted mappers."""
    from .binning import bin_columns
    return bin_columns(mappers, arr, dtype)


def _fit_mappers(ds, sample, f, cat_idx, max_bin, min_data_in_bin,
                 use_missing, zero_as_missing, forcedbins_filename,
                 max_bin_by_feature):
    """Fit per-feature BinMappers from a sample (shared by the in-memory
    and streaming construction paths)."""
    total_sample_cnt = len(sample)
    # user-forced bin boundaries, JSON list of {"feature": i,
    # "bin_upper_bound": [...]} (reference: forcedbins_filename,
    # DatasetLoader::GetForcedBins dataset_loader.cpp:1493)
    forced: Dict[int, np.ndarray] = {}
    if forcedbins_filename:
        import json as _json
        with open(forcedbins_filename) as fh:
            for entry in _json.load(fh):
                forced[int(entry["feature"])] = np.asarray(
                    entry["bin_upper_bound"], np.float64)
    if max_bin_by_feature is not None and len(max_bin_by_feature) != f:
        raise ValueError("max_bin_by_feature needs one entry per feature")
    mappers: List[BinMapper] = []
    for j in range(f):
        col = sample[:, j]
        mb = (int(max_bin_by_feature[j])
              if max_bin_by_feature is not None else max_bin)
        if j in cat_idx:
            m = find_bin_categorical(col, mb, min_data_in_bin)
        else:
            m = find_bin_numerical(
                col,
                total_sample_cnt,
                mb,
                min_data_in_bin,
                use_missing=use_missing,
                zero_as_missing=zero_as_missing,
                forced_bounds=forced.get(j),
            )
        mappers.append(m)
    ds.mappers = mappers
    ds.used_features = [j for j, m in enumerate(mappers) if not m.is_trivial]
    if not ds.used_features:
        log.warning("all features are constant; no informative splits "
                    "possible")
    # pad the bin axis to a shape-stable max_bin+1 so the jitted tree
    # grower's compile key doesn't depend on the realized bin counts
    ds.max_num_bins = max(max_bin + 1, 2)


def _apply_bundles(binned, info, ds, max_conflict_rate=1e-4):
    from .efb import bundle_matrix
    dbins = np.array([m.default_bin for m in ds.mappers], np.int32)
    out = bundle_matrix(binned, info, dbins, max_conflict_rate)
    if out is None:
        log.warning("EFB: feature conflict outside the planning sample; "
                    "keeping the dense matrix")
        ds.bundle_info = None
        return binned
    ds.bundle_info = info
    return out


# -- 4-bit dense bin packing (reference: the 4-bit mode of the dense bin
# store, src/io/dense_bin.hpp DenseBin<true>: two bins per byte) -----------
def pack4_eligible(mappers) -> bool:
    """True when every feature's realized bin count fits a nibble, so the
    bin matrix can store two columns per byte (``tpu_bin_pack4``). The
    check is per-ORIGINAL-feature: prediction inputs are binned in
    original feature space, so EFB bundling of the training matrix does
    not affect eligibility. (Training eligibility is the STORED-column
    twin — :func:`pack4_train_eligible`.)"""
    return bool(mappers) and all(m.num_bins <= 16 for m in mappers)


def pack4_train_eligible(stored_num_bins, hist_bins: int) -> bool:
    """Training-side pack4 eligibility (``tpu_bin_pack4`` on the compact
    grower): every STORED column's realized bin count must fit a nibble —
    under EFB that is the bundle-column width, which can exceed the
    members' own bins — and the shape-stable histogram width
    (``max_bin + 1``) must too, because the one-hot compare and the
    routing predicate read nibble values 0..15."""
    nb = np.asarray(stored_num_bins)
    return bool(nb.size) and int(nb.max()) <= 16 and int(hist_bins) <= 16


def pack4_matrix(binned: np.ndarray) -> np.ndarray:
    """[N, F] u8 (all values < 16) -> [N, ceil(F/2)] u8 nibble-packed.

    Column ``2j`` lands in the low nibble of packed column ``j``,
    ``2j+1`` in the high nibble — the layout ops/packed.py unpack4 and
    the predict walk's nibble gather invert. Halves the HBM footprint of
    a served request matrix."""
    if binned.dtype != np.uint8:
        raise ValueError("pack4_matrix needs a uint8 bin matrix")
    if binned.shape[1] % 2:
        binned = np.pad(binned, ((0, 0), (0, 1)))
    return (binned[:, 0::2] | (binned[:, 1::2] << 4)).astype(np.uint8)


def unpack4_matrix(packed: np.ndarray, num_features: int) -> np.ndarray:
    """Host inverse of ``pack4_matrix`` (round-trip tested)."""
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    out = np.empty((packed.shape[0], packed.shape[1] * 2), np.uint8)
    out[:, 0::2] = lo
    out[:, 1::2] = hi
    return out[:, :num_features]


def _resolve_categorical(
    categorical_feature: Optional[Sequence[Union[int, str]]],
    feature_names: List[str],
) -> set:
    out: set = set()
    if categorical_feature is None or categorical_feature == "auto" or categorical_feature == "":
        return out
    if isinstance(categorical_feature, str):
        categorical_feature = [c.strip() for c in categorical_feature.split(",") if c.strip()]
    for c in categorical_feature:
        if isinstance(c, (int, np.integer)):
            out.add(int(c))
        elif isinstance(c, str):
            if c.startswith("name:"):
                c = c[5:]
            if c in feature_names:
                out.add(feature_names.index(c))
            else:
                try:
                    out.add(int(c))
                except ValueError:
                    log.warning(f"Unknown categorical feature: {c}")
    return out
