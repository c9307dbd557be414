"""The program's own spans and counters (``repro.spans``), as recorded in
this process: set-up and the window.  A program that records none gives an
empty table, so every reader of it finds nothing."""


def totals() -> dict:
    """``{name: (count, seconds)}``, or ``{}`` where the program has no
    ``repro.spans``."""
    try:
        from repro.spans import totals
    except ImportError:
        return {}
    return totals()


def seconds(name: str):
    """Seconds of span ``name`` summed over its calls, or None."""
    row = totals().get(name)
    return row[1] if row else None

