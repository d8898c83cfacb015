"""Model families: what the benchmark knows of one kind of model.

A configuration file (``flamebench/configs/<config>.json``) names its family
with a top-level ``"family"`` key, and ``harness.family`` loads
``flamebench/families/<family>.py`` by path.  Everything else in the file is
read by the harness alike for every family: ``model`` (its ``vocab_size`` is
the catalog the traffic draws item ids from), ``n_history``, ``max_slate``
and ``engine`` (the options of ``create_engine("flame", ...)``).

A family module provides these roles:

``program_config(conf)``
    The program's model config for the configuration ``conf``.
``layout(model)``
    The weights: leaf path -> (shape, dtype name, init rule), the rules
    those of ``weights.make_params``.
``reference_row(req, n_history)``
    What the plain reference needs from one ``traffic.Request``.
``reference_scores(params, model, rows, max_slate, *, lowp=False)``
    The reference's answer for each of a block of such rows, run as one
    program at ``max_slate`` candidates; with ``lowp`` the control, in the
    precision below the served one.
``request_flops(model, n_history, m, *, new_user, grew)``
    Model FLOPs that one request of ``m`` candidates needs.
``KERNELS``
    ``{name: Kernel}``: the kernels the family runs, by the name the
    metrics use (``<name>_roofline``).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

#: the names every family module defines
ROLES = ("program_config", "layout", "reference_row", "reference_scores",
         "request_flops", "KERNELS")


class Kernel(NamedTuple):
    """One kernel of a family, as the trace shows it."""

    #: prefix of the HLO text of its calls in the device trace
    op: str
    #: ``parse(op_text)``: the call's extents as a dict, or None
    parse: Callable[[str], Optional[dict]]
    #: ``work(call, model, n_history, counters)``: the (FLOPs, bytes) the
    #: call needs at least, given the traced window's program counters
    work: Callable[[dict, dict, int, dict], Tuple[float, float]]
