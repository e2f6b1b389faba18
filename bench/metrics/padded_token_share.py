"""Share of the tokens the window's launches computed that no document
needed: bucket padding, launch-width padding, document tokens past the
stage's true fraction and prefixes computed again (scheduler, from
launch shapes).  Computed tokens are width x (new tokens + operation
tokens) per launch; needed tokens are the same launches' required count
(``work.py``)."""


def read(run):
    launches = run.launched_in_window()
    computed = sum(l.computed_tokens for l in launches)
    needed = sum(l.required["tokens"] for l in launches)
    if not computed:
        return None
    return 100.0 * (1.0 - needed / computed)
