"""Hardened subprocess execution for bench.py's measurement children.

A hung grandchild holding an inherited pipe can block a parent's read even
after the child is killed. So every guarded child runs in its OWN process
group with stdout redirected to a FILE, and timeout kills the whole group.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
from typing import List, Optional


class GuardedChild:
    """A subprocess in its own process group, stdout+stderr -> temp file."""

    def __init__(self, argv: List[str], env: Optional[dict] = None,
                 tag: str = "child"):
        self.tag = tag
        fd, self.out_path = tempfile.mkstemp(suffix=".guarded")
        os.close(fd)
        self._out_f = open(self.out_path, "w")
        self.proc = subprocess.Popen(
            argv, env=env, stdout=self._out_f, stderr=subprocess.STDOUT,
            start_new_session=True)
        self.done = False
        self._text: Optional[str] = None

    def exited(self) -> bool:
        if not self.done and self.proc.poll() is not None:
            self.done = True
        return self.done

    def text(self) -> str:
        """Current child output. Safe to call at any point — reads the file,
        never a pipe."""
        try:
            self._out_f.flush()
        except ValueError:
            pass
        try:
            return open(self.out_path).read()
        except OSError:
            return self._text or ""

    def kill(self) -> str:
        """Kill the whole process group; returns final output. The output
        file is parsed/captured BEFORE unlinking even if the child cannot
        be reaped (uninterruptible D state). killpg runs even when the
        direct child already exited: a crashed child may leave a hung
        helper process alive in its group (the round-1/2 hazard)."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except OSError:
            pass
        if not self.exited():
            try:
                self.proc.wait(timeout=10)
                self.done = True
            except subprocess.TimeoutExpired:
                sys.stderr.write(f"{self.tag}: unreaped after SIGKILL\n")
        self._text = self.text()
        try:
            self._out_f.close()
        except OSError:
            pass
        try:
            os.unlink(self.out_path)
        except OSError:
            pass
        return self._text

    def kill_group_only(self) -> None:
        """Best-effort group kill without blocking (for exit watchdogs)."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except OSError:
            pass
