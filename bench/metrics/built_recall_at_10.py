"""built_recall_at_10: recall@10 of the graph built in the window, at the
configuration's beam on its fixed probe queries, against the exact brute
force over the inserted rows."""


def read(run):
    return run.built_recall
