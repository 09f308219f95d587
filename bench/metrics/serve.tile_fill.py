"""Real query rows over the rows the engine's serve calls computed
(``serve_calls`` x ``query_block``): what micro-batching fills."""


def read(ctx):
    st = ctx.stash
    if not st.get("serve_calls"):
        return None
    return 100.0 * st["real_rows"] / (st["serve_calls"]
                                      * ctx.config["query_block"])
