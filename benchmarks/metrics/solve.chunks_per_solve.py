"""Dispatches a whole-system solve of the window took: the program's
``solve.chunk`` spans (one dispatch + one fetch of ``solve_arrays``'
loop each) that began inside the window / the solves it fetched.  A
solve of more rounds than one chunk holds pays the fetch, the progress
census and the carry hand-back once per chunk."""

from lib.scopes import program_spans


def read(run):
    chunks = program_spans(run, "solve.chunk", in_window=True)
    solves = run.record.get("solves")
    if not chunks or not solves:
        return None
    return len(chunks) / solves
