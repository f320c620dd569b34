"""Output tokens emitted in the window (first tokens from prefill and every
decoded token, of every request) over the window's seconds."""


def read(run):
    win = run.win
    n = sum(sum(1 for t in s.times if win.t0 <= t <= win.t_end) for s in win.served)
    return n / win.seconds
