"""Share (%) of the token rows the window's prefill calls computed that
hold a real prompt token, as the program counts them: the ``rows`` and
``real_rows`` of its ``engine.prefill`` spans (rows: the call's token
array; real rows: prompt tokens among them, without left padding or
empty slots). Holds for any prefill shape; nothing where no span in the
window carries the counts."""


def read(r):
    rows = real = 0
    for name, _, _, args in r.spans or []:
        if name == "engine.prefill" and "rows" in args:
            rows += args["rows"]
            real += args["real_rows"]
    return 100.0 * real / rows if rows else None
