"""Share (%) of the prefill rows in the window that hold a real prompt
token: prompt tokens of the requests whose first token came in the
window, over prefill steps x slots x prefill chunk (every prefill call
runs the fixed (slots, chunk) shape). The prefill steps are the
program's ``engine.prefill`` spans in the window, the prompt tokens the
benchmark's own requests; nothing without a prefill."""


def read(r):
    c = r.counts
    if not c.get("prefill_steps") or c["prefill_chunk"] != r.cell.traffic[
            "prefill_len"]:
        return None
    rows = c["prefill_steps"] * c["slots"] * c["prefill_chunk"]
    return 100.0 * c["prefill_prompt_tokens"] / rows
