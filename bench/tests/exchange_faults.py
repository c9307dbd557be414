"""Run one cell with the exchange between its chips broken underneath.

  python bench/tests/exchange_faults.py <fault> <bench/run.py arguments>

The flow of ``faults.py``, for cells on a mesh of more than one node: the
fault breaks the program, then the whole run goes on as usual; its check
must read ``correct: false``.
"""
import sys

import faults  # noqa: F401  (puts the benchmark and the program on the path)


def exchange_dropped():
    """What the node exchange delivers is replaced with zeros: every SpMV
    runs as if each node's block of the operator were the whole of it."""
    import jax.numpy as jnp

    from repro.core.transport import A2ATransport
    exchange = A2ATransport.exchange

    def dropped(self, x_mine, F, **kw):
        return jnp.zeros_like(exchange(self, x_mine, F, **kw))
    A2ATransport.exchange = dropped


FAULTS = {f.__name__: f for f in (exchange_dropped,)}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
