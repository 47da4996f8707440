"""TensorBuffer — the unit of data flowing through a pipeline.

Reference parity: a GstBuffer holding up to 16 GstTensorMemory chunks plus
PTS/duration (tensor_typedef.h:216-223, :35). Re-designed for TPU:

- Payloads are arrays, not byte blobs: numpy on the host path, `jax.Array`
  once a filter has staged them on device. Elements never copy; they pass
  array references (the reference achieves the same with GstMemory
  ref-counting and map/unmap).
- A buffer downstream of a filter may keep its tensors on device; the
  conversion back to host happens lazily at a sink/decoder boundary, so a
  converter→transform→filter→decoder chain does exactly one H2D and one
  D2H transfer per frame.
- `meta` carries out-of-band routing info (e.g. edge client_id — the
  GstMetaQuery analog, gst/nnstreamer/tensor_meta.c).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from nnstreamer_tpu.tensor.dtypes import DType
from nnstreamer_tpu.tensor.info import TensorFormat, TensorInfo, TensorsSpec


def _is_jax_array(x) -> bool:
    # Duck-typed so the tensor core never imports jax.
    return type(x).__module__.startswith("jax")


@dataclass
class TensorBuffer:
    tensors: Tuple[Any, ...]              # numpy arrays or jax.Arrays
    pts: Optional[int] = None             # presentation time, ns
    duration: Optional[int] = None        # ns
    format: TensorFormat = TensorFormat.STATIC
    meta: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        self.tensors = tuple(self.tensors)

    # -- constructors ------------------------------------------------------
    @classmethod
    def of(cls, *arrays, pts: Optional[int] = None, **kw) -> "TensorBuffer":
        return cls(tensors=tuple(arrays), pts=pts, **kw)

    # -- structure ---------------------------------------------------------
    @property
    def num_tensors(self) -> int:
        return len(self.tensors)

    def spec(self) -> TensorsSpec:
        """Runtime type of this buffer (for validation against negotiation)."""
        infos = []
        for t in self.tensors:
            infos.append(TensorInfo(shape=tuple(t.shape), dtype=DType.from_np(t.dtype)))
        return TensorsSpec(tensors=tuple(infos), format=self.format)

    def matches(self, spec: TensorsSpec) -> bool:
        return self.spec().is_compatible(spec)

    # -- device residency --------------------------------------------------
    @property
    def on_device(self) -> bool:
        return any(_is_jax_array(t) for t in self.tensors)

    def to_host(self) -> "TensorBuffer":
        """Materialize all tensors as numpy (the one D2H point per frame)."""
        if not self.on_device:
            return self
        host = tuple(np.asarray(t) for t in self.tensors)
        return replace(self, tensors=host, meta=dict(self.meta))

    def prefetch_host(self) -> "TensorBuffer":
        """Start async D2H copies for device tensors (copy_to_host_async).

        Non-blocking; a later to_host() then completes from the host
        staging buffer instead of paying the full transfer latency: the
        transfer overlaps compute of other in-flight frames. The
        scheduler calls it when a buffer is queued toward a
        host-consuming element (Element.WANTS_HOST)."""
        for t in self.tensors:
            fn = getattr(t, "copy_to_host_async", None)
            if fn is not None:
                try:
                    fn()
                except Exception:
                    pass   # best-effort: to_host() remains correct
        return self

    # -- functional updates ------------------------------------------------
    def with_tensors(self, tensors: Sequence[Any], **kw) -> "TensorBuffer":
        """New buffer with same timing, copied meta, different payload."""
        kw.setdefault("meta", dict(self.meta))
        return replace(self, tensors=tuple(tensors), **kw)

    def with_meta(self, **meta) -> "TensorBuffer":
        merged = dict(self.meta)
        merged.update(meta)
        return replace(self, meta=merged)

    def subset(self, indices: Sequence[int]) -> "TensorBuffer":
        """Pick tensors by index (input/output-combination analog,
        tensor_filter.c:697-735)."""
        if any(i < 0 or i >= self.num_tensors for i in indices):
            raise IndexError(
                f"tensor index out of range: buffer has {self.num_tensors} "
                f"tensors, requested {list(indices)}"
            )
        picked = tuple(self.tensors[i] for i in indices)
        return replace(self, tensors=picked, meta=dict(self.meta))

    def __repr__(self) -> str:
        shapes = ",".join(
            f"{np.dtype(t.dtype).name if not _is_jax_array(t) else t.dtype.name}"
            f"{list(t.shape)}" for t in self.tensors
        )
        where = "dev" if self.on_device else "host"
        return f"TensorBuffer({shapes} @{self.pts} {where})"


def now_ns() -> int:
    return time.monotonic_ns()
