"""`BufferReporter`: a tracer sink that keeps the finished spans, for the
tests that read them. The program's default sink counts spans and keeps
none (`utils/tracing.CountingReporter`); a test that reads `.spans`
installs this one itself."""
from collections import deque

from openwhisk_tpu.utils.tracing import Reporter, Span


class BufferReporter(Reporter):
    """Ring-shaped: a full buffer evicts the OLDEST span so the NEWEST
    survive. Evictions count as `dropped_spans` (like ZipkinReporter)."""

    def __init__(self, max_spans: int = 10_000):
        self.spans = deque(maxlen=max(1, max_spans))
        self.max_spans = max_spans
        self.sent_spans = 0
        self.dropped_spans = 0

    def report(self, span: Span) -> None:
        if len(self.spans) >= self.max_spans:
            self.dropped_spans += 1
        self.spans.append(span)
        self.sent_spans += 1
