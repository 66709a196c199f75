"""Payload `agent_sessions`: an agent platform's traffic — a few
tenants' long system prompts, many sessions under each, every session
a growing script of short tool turns.
spec: {"kind": "agent_sessions", "system_prompts": 16, "system_tokens":
8192, "sessions_per_prompt": 6, "script_tokens": 8192, "first_tokens":
[64, 384], "add_tokens": [64, 384], "short_tokens": [1, 8], "round": 8,
"align_min": 64, "page": 128, "shape_seed": n}.

A system prompt is BOS + (system_tokens - 1) printable bytes under the
program's byte tokenizer: whole pages.  A session belongs to one
system prompt and has a SCRIPT of script_tokens printable bytes; its
turn k asks its system prompt followed by the script's first ends[k]
tokens — so every turn's prompt is the turn before's and a little
more, as an agent resends its history with the last tool result.
ends[0] is drawn from first_tokens; the turns after it come in ROUNDS
of `round`: all but two add add_tokens[0]..[1] tokens, one adds what
brings the whole prompt to a whole number of pages (align_min ..
align_min + page - 1 tokens), and the turn right after that one is a
SHORT tool result of short_tokens[0]..[1] tokens — the join that
resumes 1-8 tokens behind its first answer token.  Where in its round
a session's aligned + short pair sits, and every size, come from
shape_seed (the same multiset for every seed: the seed must not change
the amount of work); which session gets which row of sizes, which
system prompt it belongs to, and all contents come from --seed.
Returns {"system": [bytes per system prompt], "system_ids": [int32 ids,
BOS first], "tenant_of": int array a session, "script": [bytes per
session], "script_ids": [int32 ids per session, no BOS], "ends": [int
array per session: script tokens in each of its turns' prompts],
"short": [bool array per session: the turn is a short tool result]}."""
import numpy as np

BOS, BYTE0 = 1, 3          # the byte tokenizer: BOS 1, byte b at 3 + b


def printable(rng, n: int) -> bytes:
    return rng.integers(0x20, 0x7F, n, dtype=np.uint8).tobytes()


def ids_of(text: bytes) -> np.ndarray:
    return np.frombuffer(text, np.uint8).astype(np.int32) + BYTE0


def row_of_sizes(shape, spec: dict, total: int):
    """One session's (ends, short) from the shape generator."""
    sys_tokens, page = int(spec["system_tokens"]), int(spec["page"])
    a_lo, a_hi = (int(v) for v in spec["add_tokens"])
    s_lo, s_hi = (int(v) for v in spec["short_tokens"])
    f_lo, f_hi = (int(v) for v in spec["first_tokens"])
    n_round, a_min = int(spec["round"]), int(spec["align_min"])
    at = int(shape.integers(0, n_round - 1))    # the aligned turn's place
    ends, short = [int(shape.integers(f_lo, f_hi + 1))], [False]
    k = 0
    while True:
        place = k % n_round
        if place == at:
            add = (-(sys_tokens + ends[-1]) - a_min) % page + a_min
        elif place == at + 1:
            add = int(shape.integers(s_lo, s_hi + 1))
        else:
            add = int(shape.integers(a_lo, a_hi + 1))
        if ends[-1] + add > total:
            break
        ends.append(ends[-1] + add)
        short.append(place == at + 1)
        k += 1
    return np.asarray(ends), np.asarray(short)


def make(spec: dict, seed: int, st, prepared: dict) -> dict:
    rng = np.random.default_rng([int(seed), 5])
    shape = np.random.default_rng([int(spec.get("shape_seed", 0)), 7])
    n_sys, per = int(spec["system_prompts"]), int(spec["sessions_per_prompt"])
    n, total = n_sys * per, int(spec["script_tokens"])
    rows = [row_of_sizes(shape, spec, total) for _ in range(n)]
    order = rng.permutation(n)
    system = [printable(rng, int(spec["system_tokens"]) - 1)
              for _ in range(n_sys)]
    script = [printable(rng, total) for _ in range(n)]
    return {
        "system": system,
        "system_ids": [np.concatenate([[BOS], ids_of(t)]).astype(np.int32)
                       for t in system],
        "tenant_of": rng.permutation(np.arange(n) % n_sys),
        "script": script,
        "script_ids": [ids_of(t) for t in script],
        "ends": [rows[j][0] for j in order],
        "short": [rows[j][1] for j in order]}
