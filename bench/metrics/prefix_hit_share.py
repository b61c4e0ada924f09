"""KV cache: prompt tokens the prefix cache served (the engine's
prefill_tokens_saved counter over the window) as a share of the prompt
tokens of the requests admitted in the window."""

from bench.readers import delta


def read(rec):
    w0, w1 = rec.window
    prompts = sum(len(r.req.prompt) for r in rec.records
                  if r.admitted is not None and w0 <= r.admitted < w1)
    saved = delta(rec, "prefill_tokens_saved", "w0", "w1")
    return 100 * saved / prompts if prompts and saved else None
