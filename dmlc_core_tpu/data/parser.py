"""Streaming format parsers → RowBlock batches — capability parity with
reference ``src/data/parser.h``, ``text_parser.h``, the per-format parsers and
the factory in ``src/data.cc``.

Architecture (mirrors SURVEY §3.2): an InputSplit produces whole-record
chunks on a prefetch thread; a parser converts each chunk to a
:class:`RowBlockContainer` (natively, with OpenMP inside the C++ lib — the
reference parallelizes with OpenMP in `text_parser.h:100-115`); a
:class:`ThreadedParser` overlaps parsing with consumption via
``ThreadedIter`` (queue capacity 8, reference `parser.h:75`).

Factory: :func:`create_parser` resolves the format ("auto" → ``format=`` URI
arg, default libsvm, reference `data.cc:68-76`) through the ``ParserFactory``
registry, so new formats plug in exactly like
``DMLC_REGISTER_DATA_PARSER`` (`data.h:330`).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Iterator, Optional

import numpy as np

from .. import native
from ..io import create_input_split, URISpec
from ..telemetry import trace as teltrace
from ..utils import (DMLCError, Parameter, Registry, ThreadedIter, check,
                     field)
from . import py_parsers
from .row_block import RowBlock, RowBlockContainer

__all__ = ["ParserBase", "TextParser", "ThreadedParser", "create_parser",
           "PARSER_REGISTRY", "CSVParserParam"]

PARSER_REGISTRY = Registry.get("ParserFactory")


class ParserBase:
    """Pull-iterator of RowBlockContainers (reference ``ParserImpl`` `parser.h:24`)."""

    def __init__(self):
        self.bytes_read = 0

    def parse_next(self) -> Optional[RowBlockContainer]:
        raise NotImplementedError

    def before_first(self) -> None:
        raise NotImplementedError

    def __iter__(self) -> Iterator[RowBlockContainer]:
        while True:
            c = self.parse_next()
            if c is None:
                return
            yield c

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class CSVParserParam(Parameter):
    """CSV options (reference ``CSVParserParam`` `csv_parser.h:22-32`)."""
    format = field(str, default="csv")
    label_column = field(int, default=-1, help="column index holding the label; -1 = none")
    delimiter = field(str, default=",")


class TextParser(ParserBase):
    """Chunk→CSR text parser over an InputSplit (reference ``TextParserBase``
    `text_parser.h:25-118`).  ``parse_fn(data bytes) -> dict`` is the native
    or fallback format kernel."""

    def __init__(self, source, parse_fn: Callable[[bytes], Dict],
                 nthreads: int = 0):
        super().__init__()
        self.source = source
        self.parse_fn = parse_fn
        self.nthreads = nthreads
        #: the team size the kernel was built with (what ``_make_kernel``
        #: resolved a 0 to); 1 for a kernel that says nothing
        self.team = int(getattr(parse_fn, "nthreads", 1))
        self._bind_metrics()

    def _bind_metrics(self) -> None:
        # cache metric handles: the registry lookup is locked and this is
        # the per-chunk hot path; re-bind when the registry generation
        # changes (metrics.reset() between epochs must not orphan us)
        from ..utils.metrics import metrics
        self._m_gen = metrics.generation
        self._m_chunk = metrics.stage("parser.chunk")
        self._m_parse = metrics.stage("parser.parse")
        self._m_bytes = metrics.throughput("parser.bytes")

    def parse_next(self) -> Optional[RowBlockContainer]:
        from ..utils.metrics import metrics
        if self._m_gen != metrics.generation:
            self._bind_metrics()
        # spans that are also the stage totals: one clock pair feeds the
        # ring's record and ``parser.chunk`` / ``parser.parse``
        with teltrace.span("parser.chunk", stage=self._m_chunk) as s:
            chunk = self.source.next_chunk()
            s.attrs["bytes"] = len(chunk) if chunk is not None else 0
        if chunk is None:
            return None
        self.bytes_read += len(chunk)
        self._m_bytes.add(len(chunk))
        with teltrace.span("parser.parse", stage=self._m_parse,
                           bytes=len(chunk), nthreads=self.team):
            d = self.parse_fn(chunk)
        return RowBlockContainer.from_arrays(
            d["offsets"], d["labels"], d["indices"], d.get("values"),
            d.get("weights"), d.get("fields"),
            max_index=d.get("max_index"), max_field=d.get("max_field", 0))

    def before_first(self) -> None:
        self.source.before_first()

    def close(self) -> None:
        self.source.close()


class ThreadedParser(ParserBase):
    """Background-thread parser (reference ``ThreadedParser`` `parser.h:71-109`)."""

    def __init__(self, base: ParserBase, max_capacity: int = 8):
        super().__init__()
        self.base = base
        self._iter: ThreadedIter[RowBlockContainer] = ThreadedIter(
            max_capacity, wait_spans=("parser.prefetch.wait_slot",
                                      "parser.prefetch.wait_item"))
        self._iter.init(lambda _cell: base.parse_next(), base.before_first)

    def parse_next(self) -> Optional[RowBlockContainer]:
        out = self._iter.next()
        self.bytes_read = self.base.bytes_read
        return out

    def before_first(self) -> None:
        self._iter.before_first()

    def close(self) -> None:
        self._iter.destroy()
        self.base.close()


def _default_nthreads() -> int:
    """Parse-team size when the caller passes 0. Explicit settings win:
    ``DMLC_NUM_THREADS`` first, then ``OMP_NUM_THREADS`` (a user pinning
    OpenMP for determinism or a CPU quota must be honored). Otherwise use
    the process affinity mask (taskset/cgroup cpusets respected), with one
    exception: when affinity reports exactly 1 but that is a container
    *quota* rather than real hardware, a modest floor of 8 recovers the
    measured 2-3x parse overlap on throttled-but-multicore hosts; on a
    genuinely serial machine the extra OpenMP threads just timeslice at
    negligible cost."""
    from ..utils.parameter import env_int
    for var in ("DMLC_NUM_THREADS", "OMP_NUM_THREADS"):
        # lenient parse: a typo'd pin logs ONE warning and falls through
        # to the next source instead of raising in whatever worker thread
        # first builds a parse kernel
        n = env_int(var, 0, minimum=1) if os.environ.get(var) else 0
        if n:
            return n
    try:
        n = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        n = os.cpu_count() or 1
    return n if n > 1 else 8


def _make_kernel(fmt: str, nthreads: int, csv_param=None) -> Callable[[bytes], Dict]:
    use_native = native.available()
    if nthreads <= 0:
        nthreads = _default_nthreads()
    if fmt == "libsvm":
        kernel = (lambda b: native.parse_libsvm(b, nthreads)) if use_native \
            else (lambda b: py_parsers.parse_libsvm(b))
    elif fmt == "libfm":
        kernel = (lambda b: native.parse_libfm(b, nthreads)) if use_native \
            else (lambda b: py_parsers.parse_libfm(b))
    elif fmt == "csv":
        lc, dl = csv_param.label_column, csv_param.delimiter
        kernel = (lambda b: native.parse_csv(b, lc, dl, nthreads)) if use_native \
            else (lambda b: py_parsers.parse_csv(b, lc, dl))
    else:
        raise DMLCError(f"no parse kernel for format {fmt!r}")
    # the team size it runs with, for ``parser.parse``'s record (the
    # python fallbacks parse on the calling thread)
    kernel.nthreads = nthreads if use_native else 1
    return kernel


def _register_text_format(fmt: str, description: str) -> None:
    @PARSER_REGISTRY.register(fmt, description=description)
    def _create(uri: str, part_index: int, num_parts: int,
                extra: Dict[str, str], nthreads: int = 0,
                threaded: bool = True) -> ParserBase:
        split = create_input_split(uri, part_index, num_parts, "text")
        # parse the csv knobs ONCE: the chunk kernel and the fused
        # streampack path must read the same values by construction
        csv_param = None
        if fmt == "csv":
            csv_param = CSVParserParam()
            csv_param.init_allow_unknown(extra)
        parser: ParserBase = TextParser(
            split, _make_kernel(fmt, nthreads, csv_param), nthreads)
        # the concrete text format (+csv knobs), for consumers that can
        # fuse parse+pack natively (DeviceLoader._use_streampack)
        parser.text_format = fmt
        if csv_param is not None:
            parser.csv_label_col = csv_param.label_column
            parser.csv_delim = csv_param.delimiter
        # surface the #cachefile fragment past the split: the DeviceLoader
        # packed-page cache (pipeline.page_cache) keys its page file off it
        # — before this, the fragment was dead config on the loader path
        cache_file = URISpec(uri, part_index, num_parts).cache_file
        parser.cache_file = cache_file
        if threaded:
            parser = ThreadedParser(parser)
            parser.cache_file = cache_file
        return parser


_register_text_format("libsvm", "sparse 'label idx:val' text (reference libsvm_parser.h)")
_register_text_format("libfm", "field-aware 'label field:idx:val' text (reference libfm_parser.h)")
_register_text_format("csv", "dense csv (reference csv_parser.h)")


def create_parser(uri: str, part_index: int = 0, num_parts: int = 1,
                  parser_type: str = "auto", nthreads: int = 0,
                  threaded: bool = True) -> ParserBase:
    """Create a streaming parser (reference ``Parser<I>::Create`` `data.h:267`,
    impl ``CreateParser_`` `data.cc:62-85`)."""
    spec = URISpec(uri, part_index, num_parts)
    if parser_type == "auto":
        parser_type = spec.args.get("format", "libsvm")
    entry = PARSER_REGISTRY.find(parser_type)
    if entry is None:
        raise DMLCError(f"unknown parser format {parser_type!r}; "
                        f"registered: {PARSER_REGISTRY.list_names()}")
    return entry(uri, part_index, num_parts, spec.args, nthreads, threaded)
