"""KV pool / radix cache: prompt tokens served from cached pages over
the prompt tokens admitted in the run, in percent (the engine's
``prefix_hit_tokens`` counter).  Moves ``ttft_p90_ms``."""


def read(run):
    hits = (run.stats1["kv_cache"]["prefix_hit_tokens"]
            - run.stats0["kv_cache"]["prefix_hit_tokens"])
    prompt = sum(r.prompt_len for r in run.all_requests if r.rid is not None)
    return 100.0 * hits / prompt if prompt else None
