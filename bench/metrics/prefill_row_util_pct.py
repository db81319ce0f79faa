"""Share of the rows that the window's prefill launches computed which
carried a prompt token, in percent: the ``tokens`` over the ``rows`` args
of the program's ``serve.prefill.launch`` spans. The rest is padding to
the chunk's power-of-two width and slots with no prompt to feed. Read
from the trace by bench/program_spans.py."""
import program_spans


def reading(ps):
    args = [a for n, _, _, a in ps["spans"] if n == "serve.prefill.launch"]
    rows = sum(a["rows"] for a in args)
    if not rows:
        return None
    return 100.0 * sum(a["tokens"] for a in args) / rows


def compute(rec):
    ps = program_spans.read(rec)
    return reading(ps) if ps else None
