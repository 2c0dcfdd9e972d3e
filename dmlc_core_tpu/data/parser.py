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
    `text_parser.h:25-118`).  ``parse_fn(data bytes, nthreads) -> dict`` is
    the native or fallback format kernel; it takes its team per call."""

    def __init__(self, source, parse_fn: Callable[[bytes, int], Dict],
                 nthreads: int = 0):
        super().__init__()
        self.source = source
        self.parse_fn = parse_fn
        self.nthreads = nthreads
        serial = getattr(parse_fn, "serial", False)
        #: the team of the next call: ``nthreads``, a 0 resolved by
        #: ``_default_nthreads()``; 1 for a kernel that parses on the
        #: calling thread (the python fallbacks)
        self.team = 1 if serial else (nthreads if nthreads > 0
                                      else _default_nthreads())
        #: the team is the parser's to size (``ThreadedParser``): no
        #: explicit ``nthreads`` and no ``DMLC_NUM_THREADS`` /
        #: ``OMP_NUM_THREADS`` pin; ``team`` is then the cap
        self.team_free = (not serial and nthreads <= 0
                          and not _pinned_nthreads())
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
            d = self.parse_fn(chunk, self.team)
        return RowBlockContainer.from_arrays(
            d["offsets"], d["labels"], d["indices"], d.get("values"),
            d.get("weights"), d.get("fields"),
            max_index=d.get("max_index"), max_field=d.get("max_field", 0))

    def before_first(self) -> None:
        self.source.before_first()

    def close(self) -> None:
        self.source.close()


class ThreadedParser(ParserBase):
    """Background-thread parser (reference ``ThreadedParser`` `parser.h:71-109`).

    Its job is to stay ahead of its consumer, not to finish one chunk
    soonest: every core its parse team holds beyond that is taken from the
    stages after it.  So where the base's team is free (``team_free``), the
    team is sized per chunk from the queue this parser fills.  It starts at
    1; it doubles, up to the cap ``_default_nthreads()`` gives, before the
    next chunk whenever the consumer has waited on an empty queue since the
    last one began (``ThreadedIter.starved``, which leaves out an epoch's
    first queue-full of takes); it halves, down to 1, whenever the queue
    has been found full before ``max_capacity`` chunks in a row
    (``ThreadedIter.full_streak``).  Each resize counts in
    ``parser.team_changes``; ``parser.parse`` records each call's team."""

    def __init__(self, base: ParserBase, max_capacity: int = 8):
        super().__init__()
        self.base = base
        self._iter: ThreadedIter[RowBlockContainer] = ThreadedIter(
            max_capacity, wait_spans=("parser.prefetch.wait_slot",
                                      "parser.prefetch.wait_item"))
        produce = base.parse_next
        if getattr(base, "team_free", False):
            self._cap, base.team = base.team, 1
            self._starved_seen = 0
            produce = self._sized_parse
        self._iter.init(lambda _cell: produce(), base.before_first)

    def _sized_parse(self) -> Optional[RowBlockContainer]:
        """``base.parse_next()`` with the team resized first (on the
        producer thread, the one that reads ``base.team``)."""
        it, team = self._iter, self.base.team
        if it.starved != self._starved_seen:
            self._starved_seen = it.starved
            team = min(self._cap, 2 * team)
        elif it.full_streak and it.full_streak % it.max_capacity == 0:
            team = max(1, team // 2)
        if team != self.base.team:
            self.base.team = team
            from ..utils.metrics import metrics
            metrics.counter("parser.team_changes").add(1)
        return self.base.parse_next()

    def parse_next(self) -> Optional[RowBlockContainer]:
        out = self._iter.next()
        self.bytes_read = self.base.bytes_read
        return out

    def before_first(self) -> None:
        self._iter.before_first()

    def close(self) -> None:
        self._iter.destroy()
        self.base.close()


def _pinned_nthreads() -> int:
    """The team ``DMLC_NUM_THREADS``, else ``OMP_NUM_THREADS``, pins; 0
    when neither does."""
    from ..utils.parameter import env_int
    for var in ("DMLC_NUM_THREADS", "OMP_NUM_THREADS"):
        # lenient parse: a typo'd pin logs ONE warning and falls through
        # to the next source instead of raising in whatever worker thread
        # first builds a parse kernel
        n = env_int(var, 0, minimum=1) if os.environ.get(var) else 0
        if n:
            return n
    return 0


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
    n = _pinned_nthreads()
    if n:
        return n
    try:
        n = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        n = os.cpu_count() or 1
    return n if n > 1 else 8


def _make_kernel(fmt: str, csv_param=None) -> Callable[[bytes, int], Dict]:
    """``kernel(data, nthreads)`` of one format: the team is the call's."""
    use_native = native.available()
    if fmt == "libsvm":
        kernel = native.parse_libsvm if use_native \
            else (lambda b, _n: py_parsers.parse_libsvm(b))
    elif fmt == "libfm":
        kernel = native.parse_libfm if use_native \
            else (lambda b, _n: py_parsers.parse_libfm(b))
    elif fmt == "csv":
        lc, dl = csv_param.label_column, csv_param.delimiter
        kernel = (lambda b, n: native.parse_csv(b, lc, dl, n)) if use_native \
            else (lambda b, _n: py_parsers.parse_csv(b, lc, dl))
    else:
        raise DMLCError(f"no parse kernel for format {fmt!r}")
    if not use_native:
        kernel.serial = True    # parses on the calling thread: a team of 1
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
            split, _make_kernel(fmt, csv_param), nthreads)
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
