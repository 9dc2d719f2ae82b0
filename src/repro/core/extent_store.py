"""ExtentStore — device-resident analytics data, in stacked pages.

The analytics sibling of ``core.kv_tier.PageStore``: one pool of
stacked pages ``[n_pages, page_rows, n_cols]`` (float32) holds every
extent's rows, an *extent* is a named run of physical pages plus a row
count, and the jitted scan/filter/reduce kernel
(``kernels.isp_scan``) consumes the pool directly through a per-extent
page table — the flash the paper's ISP-containers process in place.

A MiniDocker analytics app is no longer an opaque callable: it is an
:class:`AnalyticsJob` — a declarative scan -> filter -> reduce program
that serializes to JSON (so it rides Ether-oN job frames and λFS
rootfs params) and executes as one jitted Pallas kernel over the
node's extent pages.  The registered ``isp-analytics`` image is the
single generic interpreter for these programs.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.container import (ContainerError, ImageManifest, make_blob,
                                  register_app)
from repro.core.kv_tier import (PAGE_DTYPES, _fp8_dtype, dequantize_page_kv,
                                quantize_page_kv)
from repro.kernels import ops
from repro.kernels.isp_scan import (BIG_ID, FILTER_OPS, MAX_TOPK,
                                    REDUCE_ROWS, TOPK_METRICS, topk_pad)

#: the generic analytics image every DockerSSD runs (entry = the program
#: interpreter below)
ANALYTICS_IMAGE = "isp-analytics"

#: host-side projections of the kernel's aggregate block ("topk" runs
#: the scored-scan reducer instead of scan/filter/reduce)
REDUCE_KINDS = ("count", "sum", "min", "max", "avg", "table", "topk")


class ExtentStoreError(Exception):
    pass


@dataclasses.dataclass
class Extent:
    name: str
    page_ids: List[int]
    n_rows: int
    n_cols: int                     # logical columns (<= store n_cols)
    # stored bytes per row (codes + per-row scale for quantized
    # stores); None falls back to f32 rows — keeping old pickles valid
    row_bytes: Optional[int] = None

    @property
    def nbytes(self) -> int:
        """Stored bytes the host baseline must move to read this —
        dtype-aware, so the OffloadPlanner prices quantized extent
        reads at their (smaller) real transfer size."""
        if self.row_bytes is not None:
            return self.n_rows * self.row_bytes
        return self.n_rows * self.n_cols * 4


class ExtentStore:
    """One DockerSSD's flash-resident analytics pages.

    ``pages``: [n_pages, page_rows, n_cols] float32.  Extents are
    page-granular allocations out of a free list (mirroring λFS block
    allocation); the kernel addresses them through per-extent page
    tables, so extents never need to be physically contiguous.
    ``device`` pins the pool to one device (a pool node's own chip);
    None leaves it on the default device.
    """

    def __init__(self, *, n_pages: int = 64, page_rows: int = 128,
                 n_cols: int = 128, page_dtype: str = "fp32",
                 device=None):
        if page_dtype not in PAGE_DTYPES:
            raise ValueError(f"page_dtype must be one of {PAGE_DTYPES}, "
                             f"got {page_dtype!r}")
        if page_dtype == "fp8" and _fp8_dtype() is None:
            raise ValueError("page_dtype='fp8' needs jnp.float8_e4m3fn "
                             "(unavailable on this jax build); use 'int8'")
        self.n_pages = n_pages
        self.page_rows = page_rows
        self.n_cols = n_cols
        self.page_dtype = page_dtype
        self.quantized = page_dtype in ("int8", "fp8")
        if page_dtype == "int8":
            self.code_dtype, self.qmax = jnp.int8, 127.0
        elif page_dtype == "fp8":
            self.code_dtype, self.qmax = _fp8_dtype(), 448.0
        else:
            self.code_dtype, self.qmax = jnp.float32, 0.0
        self.pages = jax.device_put(
            jnp.zeros((n_pages, page_rows, n_cols), self.code_dtype), device)
        # per-row scales of a quantized pool (1.0 keeps untouched pages
        # dequantizing to zero); None for full precision
        self.scales = (jax.device_put(
            jnp.ones((n_pages, page_rows), jnp.float32), device)
            if self.quantized else None)
        self.extents: Dict[str, Extent] = {}
        self._free: List[int] = list(range(n_pages))

    # -- capacity ------------------------------------------------------------

    @property
    def row_nbytes(self) -> int:
        """Stored bytes per row: codes (+ the row's f32 scale when
        quantized)."""
        per = self.n_cols * jnp.dtype(self.code_dtype).itemsize
        return per + (4 if self.quantized else 0)

    @property
    def page_nbytes(self) -> int:
        return self.page_rows * self.row_nbytes

    def free_pages(self) -> int:
        return len(self._free)

    # -- extent life cycle ----------------------------------------------------

    def put(self, name: str, arr: np.ndarray) -> Extent:
        """Ingest host data as a new extent (pad rows to page granularity,
        pad columns to the store width)."""
        arr = np.asarray(arr, np.float32)
        if arr.ndim != 2:
            raise ExtentStoreError(f"extent data must be 2-D [rows, cols], "
                                   f"got shape {arr.shape}")
        rows, cols = arr.shape
        if cols > self.n_cols:
            raise ExtentStoreError(f"extent has {cols} cols; store width "
                                   f"is {self.n_cols}")
        if name in self.extents:
            raise ExtentStoreError(f"extent {name!r} already exists")
        need = -(-max(rows, 1) // self.page_rows)
        if need > len(self._free):
            raise ExtentStoreError(
                f"ENOSPC: extent {name!r} needs {need} pages, "
                f"{len(self._free)} free")
        ids = [self._free.pop(0) for _ in range(need)]
        padded = np.zeros((need * self.page_rows, self.n_cols), np.float32)
        padded[:rows, :cols] = arr
        blocks = padded.reshape(need, self.page_rows, self.n_cols)
        idx = jnp.asarray(ids, jnp.int32)
        if self.quantized:
            # per-row symmetric quantization at ingest: the flash holds
            # codes + a [page_rows] scale column per page
            codes, scale = quantize_page_kv(jnp.asarray(blocks),
                                            self.qmax, self.code_dtype)
            self.pages = self.pages.at[idx].set(codes)
            self.scales = self.scales.at[idx].set(scale)
        else:
            self.pages = self.pages.at[idx].set(jnp.asarray(blocks))
        ext = Extent(name, ids, rows, cols, row_bytes=self.row_nbytes)
        self.extents[name] = ext
        return ext

    def get(self, name: str) -> np.ndarray:
        """Read a whole extent back to the host (the baseline's full
        transfer; the ISP path never calls this).  Quantized extents
        dequantize host-side — the same elementwise f32 multiply the
        kernel applies per page in VMEM, so a page-sequential fold over
        this array is bit-identical to the in-storage path."""
        ext = self._extent(name)
        idx = jnp.asarray(ext.page_ids, jnp.int32)
        pages = self.pages[idx]
        if self.quantized:
            pages = dequantize_page_kv(pages, self.scales[idx])
        flat = np.asarray(pages).reshape(-1, self.n_cols)
        return flat[:ext.n_rows, :ext.n_cols]

    def raw_extent(self, name: str):
        """The extent as stored: ``(codes [n_rows, n_cols], scales
        [n_rows] | None)`` — what crosses the wire on a remote read
        (Ether-oN data frames ship the quantized bytes, never an
        inflated f32 copy; the reader dequantizes at the far end)."""
        ext = self._extent(name)
        idx = jnp.asarray(ext.page_ids, jnp.int32)
        codes = np.asarray(self.pages[idx]).reshape(-1, self.n_cols)
        codes = codes[:ext.n_rows, :ext.n_cols]
        if not self.quantized:
            return codes, None
        scales = np.asarray(self.scales[idx]).reshape(-1)[:ext.n_rows]
        return codes, scales

    def drop(self, name: str):
        ext = self.extents.pop(name, None)
        if ext is not None:
            self._free.extend(ext.page_ids)

    def page_table(self, name: str) -> jnp.ndarray:
        return jnp.asarray(self._extent(name).page_ids, jnp.int32)

    def _extent(self, name: str) -> Extent:
        if name not in self.extents:
            raise ExtentStoreError(f"no extent {name!r}")
        return self.extents[name]


# ---------------------------------------------------------------------------
# the analytics program (what a MiniDocker app now *is*)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AnalyticsJob:
    """A declarative scan -> filter -> reduce program over one extent.

    Serializes to JSON, so the same object rides the docker-cli front
    door (``start?job=...``), Ether-oN job frames, and λFS rootfs
    params.  ``reduce`` picks the host-visible projection of the
    kernel's aggregate block; ``table`` returns the full block (what
    the correctness contract compares bit-for-bit)."""
    extent: str
    filter_col: int = 0
    filter_op: str = "all"          # one of kernels.isp_scan.FILTER_OPS
    threshold: float = 0.0
    reduce: str = "table"           # one of REDUCE_KINDS
    reduce_col: int = 0
    job_id: int = 0
    # operator intensity hint: effective GB/s the operator scans at on
    # the host (0 = the planner's default).  Low values mark a
    # compute-bound operator — the per-request input that flips the
    # offload decision to the host (Fig 11's losing regime).
    scan_gbs: float = 0.0
    # retrieval (reduce="topk"): the query vector (zero-padded to the
    # store width at execution), result count, and scoring metric
    query: Optional[List[float]] = None
    k: int = 0
    metric: str = "dot"             # one of kernels.isp_scan.TOPK_METRICS

    def validate(self):
        if self.filter_op not in FILTER_OPS:
            raise ContainerError(f"bad filter_op {self.filter_op!r}; "
                                 f"expected one of {FILTER_OPS}")
        if self.reduce not in REDUCE_KINDS:
            raise ContainerError(f"bad reduce {self.reduce!r}; "
                                 f"expected one of {REDUCE_KINDS}")
        if self.reduce == "topk":
            if not self.query:
                raise ContainerError("topk job needs a query vector")
            if not 1 <= self.k <= MAX_TOPK:
                raise ContainerError(f"topk k must be in [1, {MAX_TOPK}], "
                                     f"got {self.k}")
            if self.metric not in TOPK_METRICS:
                raise ContainerError(f"bad metric {self.metric!r}; "
                                     f"expected one of {TOPK_METRICS}")
        elif self.query is not None:
            raise ContainerError(f"query only applies to reduce='topk', "
                                 f"not {self.reduce!r}")
        return self

    def padded_query(self, n_cols: int) -> np.ndarray:
        """The query zero-padded to the executing store's width — the
        same padding ``ExtentStore.put`` applied to narrow extents, so
        padded columns contribute 0 to every score on both paths."""
        qv = np.asarray(self.query, np.float32)
        if qv.ndim != 1 or qv.shape[0] > n_cols:
            raise ContainerError(f"query must be 1-D with <= {n_cols} "
                                 f"entries, got shape {qv.shape}")
        q = np.zeros((n_cols,), np.float32)
        q[:qv.shape[0]] = qv
        return q

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "AnalyticsJob":
        return AnalyticsJob(**d).validate()


def project(block: np.ndarray, job: AnalyticsJob):
    """Host-side projection of the kernel's [8, n_cols] aggregate."""
    if job.reduce == "table":
        return block
    if job.reduce == "topk":
        # [[row_id, score], ...] best-first; (NEG_INF, BIG_ID) empty
        # slots (k > n_rows) are dropped
        scores, ids = block[0], block[1]
        return [[int(i), float(s)]
                for i, s in zip(ids[:job.k], scores[:job.k]) if i < BIG_ID]
    if job.reduce == "count":
        return float(block[0, 0])
    col = job.reduce_col
    if job.reduce == "sum":
        return float(block[1, col])
    if job.reduce == "min":
        return float(block[2, col])
    if job.reduce == "max":
        return float(block[3, col])
    n = block[0, 0]
    return float(block[1, col] / n) if n else float("nan")   # avg


def analytics_blob() -> bytes:
    """The docker blob every node pulls: the generic analytics image."""
    return make_blob(
        ImageManifest(ANALYTICS_IMAGE, ANALYTICS_IMAGE,
                      ["kernel-layer", "runtime-layer"],
                      config={"kernel": "scan_filter_reduce"}),
        {"kernel-layer": b"pallas scan/filter/reduce",
         "runtime-layer": b"job interpreter"})


@register_app(ANALYTICS_IMAGE)
def isp_analytics(ctx, jobs=None, job_pages=None):
    """The containerized analytics interpreter.

    Parameters arrive the D-VirtFW way: packaged in the container's
    rootfs (λFS ``job.json``, read through function-call syscalls — no
    Kernel-ctx) with the raw call args staged in the MPU-checked ISP
    memory pool.  Each job executes as one jitted Pallas
    ``scan_filter_reduce`` over the node's extent pages and returns the
    reduced aggregate — the only bytes that travel back to the host.
    """
    if jobs is None:
        # rootfs-packaged params: /containers/<cid>/rootfs/job.json
        fd = ctx.syscall("openat", f"/containers/{ctx.c.cid}/rootfs/job.json")
        raw = ctx.syscall("read", fd)
        ctx.syscall("close", fd)
        jobs = json.loads(raw)
    jobs = [j if isinstance(j, AnalyticsJob) else AnalyticsJob.from_dict(j)
            for j in jobs]
    if job_pages is not None:
        # call args staged in the ISP pool (user-mode readable; the FW
        # pool would trap) — verify the MPU-checked buffer round-trips.
        # Compare canonicalized: clients may send sparse dicts and let
        # AnalyticsJob defaults fill the rest.
        staged = [AnalyticsJob.from_dict(d).to_dict()
                  for d in json.loads(ctx.fw.read_job(job_pages))]
        if staged != [j.to_dict() for j in jobs]:
            raise ContainerError("ISP-pool job buffer does not match "
                                 "rootfs params")
    store = ctx.extents
    if store is None:
        raise ContainerError("node has no ExtentStore attached")
    results = []
    for job in jobs:
        if job.extent not in store.extents:
            raise ContainerError(f"no extent {job.extent!r} on this node")
        # cgroup accounting: one VMEM-resident page + the aggregate
        out_cols = topk_pad(job.k) if job.reduce == "topk" else store.n_cols
        work = store.page_nbytes + REDUCE_ROWS * out_cols * 4
        ctx.alloc(work)
        try:
            if job.reduce == "topk":
                block = ops.topk_scan(
                    store.pages, store.page_table(job.extent),
                    store.extents[job.extent].n_rows,
                    job.padded_query(store.n_cols),
                    k=job.k, metric=job.metric, scales=store.scales)
            else:
                block = ops.scan_filter_reduce(
                    store.pages, store.page_table(job.extent),
                    store.extents[job.extent].n_rows, job.threshold,
                    scales=store.scales,
                    filter_col=job.filter_col, filter_op=job.filter_op)
            results.append(np.asarray(jax.block_until_ready(block)))
        finally:
            ctx.free(work)
        ctx.log(f"job {job.job_id}: scanned {job.extent} "
                f"({store.extents[job.extent].n_rows} rows) "
                f"filter={job.filter_op} -> {job.reduce}")
    return results
