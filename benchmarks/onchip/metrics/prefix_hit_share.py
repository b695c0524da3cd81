"""Share of the prompt tokens admitted in the window that sat in pages
adopted from the copy-on-write prefix index (the admission event's shared
page count times the page size, capped at the prompt), in percent. First
admissions only; resumed requests are not counted again."""


def read(run):
    hit = total = 0
    for tr in run.tracks:
        if tr.shared_tokens is None or tr.admit is None \
                or not run.in_window(tr.admit):
            continue
        hit += tr.shared_tokens
        total += tr.prompt_len
    if total == 0:
        return None
    return 100.0 * hit / total
