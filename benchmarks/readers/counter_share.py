"""One ``/metrics`` counter's increase over the window as a share, in
percent, of another's: the rows of the blocked scan that held a pod, of the
rows it laid out, say.  A counter the program does not have (the parent
commit; it registers these at boot) gives nothing, and so does a window in
which ``of`` did not move."""

import prom


def _moved(ctx, counter):
    """The counter's increase over the window, or None where no such
    series exists."""
    if not any(name == counter for name, _labels, _v in ctx["after"]):
        return None
    return prom.total(ctx["after"], counter) - prom.total(ctx["before"], counter)


def read(ctx, counter, of, **_args):
    part, whole = _moved(ctx, counter), _moved(ctx, of)
    if part is None or not whole or whole <= 0:
        return None
    return 100.0 * part / whole
