"""Device time of the graph domain's protection per PageRank iteration
of the traced chunks: the ranks' re-encode (``jit_graph_encode_rows``)
and the scrub slice (``jit_graph_scrub_slice``), packing included."""


def read(ctx):
    import programs
    return programs.module_ms(ctx, "jit_graph_encode_rows",
                              "jit_graph_scrub_slice")
