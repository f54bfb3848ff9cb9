"""Wall seconds of the program's ``sextans.plan.build`` spans in the run
(the bodies of ``sparse_api.plan`` and ``plan_group``: resolution, trace,
lower, compile or cache read).  The cells build their plans in set-up
only, so these are set-up seconds."""

from bench.program_spans import SpanSeconds

read = SpanSeconds("sextans.plan.build").read
