"""FLC008 negative corpus: spans and counters in host code stay silent.

Every call below is one FLC008 matches by name, but no jit root reaches
the function it sits in; and ``count`` methods of other objects are not
the span module's counter.  Never executed — parsed only.
"""
import jax
import jax.numpy as jnp

from repro.utils import spans


@jax.jit
def _program(x):
    return jnp.cumsum(x)


def good_driver(x, items):
    with spans.span("fl.horizon"):
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            y = _program(x)
        spans.count("driver.calls")
        spans.count("driver.items", items.count(0))
    return y


@jax.jit
def good_list_count_in_jit(x):
    # a str/list .count() is not spans.count
    n = "abc".count("a")
    return x * n
