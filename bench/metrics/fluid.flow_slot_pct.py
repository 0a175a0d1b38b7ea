"""Share of the flow slots dispatched to the fill that hold a real flow
(``CorpusStats`` flow_used over flow_slots, window delta)."""


def read(win):
    if win.corpus["flow_slots"] == 0:
        return None
    return 100.0 * win.corpus["flow_used"] / win.corpus["flow_slots"]
