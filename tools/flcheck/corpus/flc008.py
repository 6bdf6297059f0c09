"""FLC008 corpus: host spans and counters inside jit-reachable code.

A span or a count in a traced body runs once, while the function is
traced: the span times the tracing and the count counts compiles.  The
rule fires only where the enclosing function is reachable from a jit
root (FLC003's call graph).  Never executed — parsed only.
"""
import jax
import jax.numpy as jnp

from repro.utils import spans
from repro.utils.spans import count


@jax.jit
def bad_span_in_jit(x):
    with spans.span("fl.inner"):  # expect: FLC008
        return jnp.sum(x)


@jax.jit
def bad_count_in_jit(x):
    count("calls")  # expect: FLC008
    return x + 1


def _body(c, x):
    with jax.profiler.TraceAnnotation("step"):  # expect: FLC008
        return c + x, x


def bad_annotation_in_scan_body(xs):
    return jax.lax.scan(_body, 0.0, xs)


def good_span_around_the_jitted_call(x):
    # the host code that calls the program is where spans belong
    with spans.span("fl.dispatch"):
        y = bad_count_in_jit(x)
    spans.count("calls")
    return y
