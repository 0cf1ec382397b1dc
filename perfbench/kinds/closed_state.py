"""Traffic kind ``closed_state``: kind ``closed`` as it is (the same
clients, lead-in and window: ``kinds/closed.py`` runs them), for a model
that keeps a state of a *session*, with one more number in the
comparison that decides ``correct``.

**``state_gap``.**  The served tokens cannot hold the precision a state
is kept in: rounding it moves a logit less than a tipped expert in an
earlier token's layers does (PERF.md section 4 has the readings).  So the
state itself is compared.  When the window has closed, the sessions that
are decoding still lie in their slots; the state of the shallowest and
of the deepest ``STATE_SAMPLE / 2`` of them is read from the engine (the
family's ``session_states``: the state-space layers that no expert layer
precedes, so no router stands between the program's state and the
reference's) and the reference computes the same layers' state from the
same tokens, one position after another in float32
(``reference.session_states``).  The number is the widest gap ``|H -
H_ref| / |H_ref|`` of one head of one session.  The shallowest sessions
are the ones most lately admitted, into slots that other sessions left:
what a slot's last session left behind has not decayed there.  The
deepest have stepped their state most often.

``pb.serve_common.run`` closes the engine before it returns and hands a
kind no moment between the window's end and that, so the states are read
where the engine is closed (``fault`` is the one hook it gives: it is
handed the loop).  Nothing is read inside the window.
"""
from __future__ import annotations

from pb import cells, correct, weights

#: sessions whose state is compared: half the shallowest, half the deepest
STATE_SAMPLE = 8


def tiny(mix: dict, limits: dict) -> tuple:
    """``closed``'s sizes; a float32 engine's state is the reference's to
    rounding."""
    mix, limits = cells.kind_module("closed").tiny(mix, limits)
    return mix, dict(limits, state_gap=1e-5)


def _sample(sessions):
    by_depth = sorted(sessions, key=lambda s: (s.position, s.rid))
    half = STATE_SAMPLE // 2
    if len(by_depth) <= 2 * half:
        return by_depth
    return by_depth[:half] + by_depth[-half:]


def _read_at_close(cell, loop, taken):
    """Wrap ``loop.eng.close`` for one call: the sampled sessions' tokens
    and states are taken first."""
    eng = loop.eng
    close = eng.close

    def closing():
        del eng.close                       # the engine's own again
        sessions = _sample([s for s in eng.scheduler.decode_sessions()
                            if s.slot is not None])
        if sessions:
            taken["sessions"] = [
                {"rid": s.rid, "slot": s.slot, "position": s.position,
                 "ids": (list(s.request.prompt) + list(s.out))[:s.position]}
                for s in sessions]
            taken["states"] = cell.family.session_states(
                eng, cell.config, sessions)
        close()
    eng.close = closing


def state_gaps(cell, seed, taken, control=None):
    """``(sessions, heads)``: each head's ``|H - H_ref| / |H_ref|`` over
    the compared layers, of every session of ``taken``.  With ``control``
    the lower-precision reference's state stands in the program's
    place."""
    import jax.numpy as jnp
    import numpy as np
    cfg, family = cell.config, cell.family
    w = weights.make_weights(family, cfg, seed, cfg["serve"]["weights_dtype"])
    s_max = family.max_positions(cfg)
    rows = len(taken["sessions"])
    ids = np.zeros((rows, s_max), np.int32)
    lengths = np.zeros((rows,), np.int32)
    for i, s in enumerate(taken["sessions"]):
        ids[i, :len(s["ids"])] = s["ids"]
        lengths[i] = len(s["ids"])
    states = cell.reference.session_states
    want = np.asarray(states(cfg, w, jnp.asarray(ids), jnp.asarray(lengths)))
    got = taken["states"] if control is None else np.asarray(
        states(cfg, w, jnp.asarray(ids), jnp.asarray(lengths), control))
    # (sessions, layers, heads, channels, state) -> a head's norm over its
    # layers, channels and state
    off = np.sqrt(np.square(got - want, dtype=np.float64).sum((1, 3, 4)))
    return off / np.sqrt(np.square(want, dtype=np.float64).sum((1, 3, 4)))


def run(cell, args, env, fault=None, eng=None):
    taken = {}

    def plant(loop):
        if fault is not None:
            fault(loop)
        _read_at_close(cell, loop, taken)
    out = cells.kind_module("closed", cell.repo).run(
        cell, args, env, fault=plant, eng=eng)
    if "states" not in taken:
        env.say("state_gap: no decoding session held a state when the "
                "window closed")
        return out
    t = env.now()
    gaps = state_gaps(cell, args.seed, taken)
    worst = gaps.max(axis=1)
    at = int(worst.argmax())
    env.say(f"state_gap: {len(worst)} sessions' state in layers "
            f"{cell.reference.clean_state_layers(cell.config)} against the "
            f"float32 recurrence in {env.now() - t:.1f} s; widest head's "
            f"gap a session, by depth: " + ", ".join(
                f"{s['position']}: {g:.3e}"
                for s, g in zip(taken["sessions"], worst))
            + f"; the widest is session {taken['sessions'][at]['rid']}'s "
            f"(slot {taken['sessions'][at]['slot']}), head "
            f"{int(gaps[at].argmax())}")
    ok, compared = correct.judge({"state_gap": float(worst.max())},
                                 cell.settings["limits"])
    out["correct"] = out["correct"] and ok
    out["compared"].update(compared)
    out["state"] = dict(taken, gaps=gaps)
    return out
