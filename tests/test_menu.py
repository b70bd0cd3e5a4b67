"""Arrow-key config menu (reference: commands/menu/selection_menu.py) —
key handling, wrap-around, digit jumps, non-TTY fallback, and the
questionnaire end-to-end without typing a single enum value."""

import builtins
import io

import pytest

from accelerate_tpu.commands.menu import choose, select


def _run(keys, choices, default_index=0):
    it = iter(keys)
    out = io.StringIO()
    idx = select("pick one", choices, default_index=default_index,
                 reader=lambda: next(it), out=out)
    return idx, out.getvalue()


def test_select_navigation_and_enter():
    idx, out = _run(["down", "down", "enter"], ["a", "b", "c"])
    assert idx == 2
    assert "pick one" in out and "➔" in out


def test_select_wraps_both_directions():
    idx, _ = _run(["up", "enter"], ["a", "b", "c"])       # up from 0 -> last
    assert idx == 2
    idx, _ = _run(["down", "down", "down", "enter"], ["a", "b", "c"])
    assert idx == 0


def test_select_vim_keys_and_digits():
    idx, _ = _run(["j", "enter"], ["a", "b", "c"])
    assert idx == 1
    idx, _ = _run(["2"], ["a", "b", "c"])  # digit jumps AND selects
    assert idx == 1


def test_select_escape_keeps_default():
    idx, _ = _run(["down", "q"], ["a", "b", "c"], default_index=1)
    assert idx == 1


def test_choose_fallback_numbered(monkeypatch, capsys):
    monkeypatch.setenv("ACCELERATE_NO_MENU", "1")
    answers = iter(["2", "", "bf16"])
    monkeypatch.setattr(builtins, "input", lambda *_: next(answers))
    assert choose("env", ["LOCAL_MACHINE", "TPU_POD"], "LOCAL_MACHINE") == "TPU_POD"
    assert choose("env", ["LOCAL_MACHINE", "TPU_POD"], "LOCAL_MACHINE") == "LOCAL_MACHINE"
    # typing the value (old questionnaire behavior) still works
    assert choose("precision", ["no", "bf16", "fp16"], "no") == "bf16"
    out = capsys.readouterr().out
    assert "1.* LOCAL_MACHINE" in out  # default marked


def _pty_menu(keys: bytes, key_gap_s: float = 0.0):
    """Run select() in a child on a real pty, feed ``keys`` once the menu has
    rendered, return the captured output. Success is judged on output; the
    child is killed and reaped once it printed its answer."""
    import os
    import pty
    import re
    import select as _select
    import subprocess
    import sys
    import time

    code = (
        "from accelerate_tpu.commands.menu import select\n"
        "print('IDX', select('t', ['a', 'b', 'c']))\n"
    )
    master, slave = pty.openpty()
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdin=slave, stdout=slave, stderr=subprocess.DEVNULL,
        env={**os.environ, "PYTHONPATH": os.getcwd(), "JAX_PLATFORMS": "cpu"},
    )
    os.close(slave)
    out = b""
    deadline = time.time() + 60
    sent = 0  # keys written so far
    try:
        while not re.search(rb"IDX \d", out) and time.time() < deadline:
            # Only send keys once the menu rendered — writing earlier races
            # the child's tty.setraw and the bytes get canonical-echoed away.
            if sent == 0 and "➔".encode() in out:
                if key_gap_s:
                    # byte-at-a-time with gaps (bare-ESC timing cases)
                    for i in range(len(keys)):
                        os.write(master, keys[i: i + 1])
                        time.sleep(key_gap_s)
                else:
                    os.write(master, keys)
                sent = len(keys)
            r, _, _ = _select.select([master], [], [], 1.0)
            if not r:
                continue
            try:
                chunk = os.read(master, 4096)
            except OSError:
                break
            if not chunk:
                break
            out += chunk
    finally:
        os.close(master)
        proc.kill()
        proc.wait(timeout=30)
    return out


def test_tty_reader_escape_decoding_under_pty():
    out = _pty_menu(b"\x1b[B\x1b[B\x1b[A\r")  # ↓ ↓ ↑ ⏎ -> index 1
    assert b"IDX 1" in out, out[-500:]


def test_tty_reader_ss3_arrows_and_delete_ignored():
    """Application-cursor-mode arrows (\\x1bOB) must navigate, and a Delete
    key (\\x1b[3~) must be ignored — not exit the menu or leave stray bytes
    queued for the next read."""
    out = _pty_menu(b"\x1b[3~\x1bOB\r")  # Delete (ignored), SS3 ↓, ⏎ -> 1
    assert b"IDX 1" in out, out[-500:]


def test_tty_reader_bare_escape_keeps_default():
    """A lone ESC press (no trailing sequence bytes) must return the default
    immediately instead of blocking on a read for bytes that never come."""
    out = _pty_menu(b"\x1b", key_gap_s=0.3)
    assert b"IDX 0" in out, out[-500:]


def test_interactive_config_end_to_end(monkeypatch):
    """Full questionnaire without typing one enum value: numbered picks for
    choices, plain values for free-form ints."""
    from accelerate_tpu.commands.config import interactive_config

    monkeypatch.setenv("ACCELERATE_NO_MENU", "1")
    answers = iter([
        "1",    # compute environment -> LOCAL_MACHINE
        "4",    # num_processes
        "8476", # coordinator port
        "no",   # cpu only?
        "4",    # dp_shard
        "1",    # dp_replicate
        "1",    # tp
        "1",    # cp
        "1",    # sp
        "1",    # pp
        "1",    # ep
        "1",    # sharding strategy -> FULL_SHARD
        "no",   # offload
        "yes",  # activation checkpointing
        "2",    # mixed precision -> bf16
        "2",    # grad accumulation
    ])
    monkeypatch.setattr(builtins, "input", lambda *_: next(answers))
    cfg = interactive_config()
    assert cfg.compute_environment == "LOCAL_MACHINE"
    assert cfg.num_processes == 4
    assert cfg.dp_shard_size == 4
    assert cfg.use_fsdp and cfg.fsdp_sharding_strategy == "FULL_SHARD"
    assert cfg.fsdp_activation_checkpointing
    assert cfg.mixed_precision == "bf16"
    assert cfg.gradient_accumulation_steps == 2
