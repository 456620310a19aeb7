"""Kernels layer: grid programs the window layers' three flash kernels VISIT
over those that are LIVE (have an unmasked score), a head, from the arm the
program states in ``kernel_routes`` (``fwd 1024x1024 21/24, ...``). 1.0 =
only live blocks are visited; the band's grid visits the most live blocks
any one outer block has, so the first blocks of a sequence leave a
remainder (24 / 21 at S 8192, W 2048 and 1024-tiles); the causal grid would
read 64 / 21."""

import trinity_trace


def reduce(run: dict):
    return trinity_trace.window_visited_over_live(run)
