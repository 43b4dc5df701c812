"""decode_step_ms: mean milliseconds the host spends in the engine's
`decode` and `sample` spans (dispatch of the fused decode step and the
wait for its tokens) over the window's steps that decoded and ran no
prefill chunk."""


def read(run):
    steps = [s for s in run.window_steps
             if s.decode_positions and s.prefill_tokens == 0
             and "decode" in s.components]
    if not steps:
        return None
    tot = sum(s.components.get("decode", 0.0) + s.components.get("sample", 0.0)
              for s in steps)
    return 1e3 * tot / len(steps)
