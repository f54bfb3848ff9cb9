"""Wall seconds of the program's ``sextans.pack`` spans in the run
(``SextansEngine.pack``; ``SparseLinearGroup``'s ``stack_bsr`` and
upload).  The cells pack in set-up only, so these are set-up seconds."""

from bench.program_spans import SpanSeconds

read = SpanSeconds("sextans.pack").read
