"""What the chip scripts share: a process for each item (a chip belongs
to one process, and a process's weights are its seed's), and a line for
each in a file under ``chiprun_out/``."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def each_in_a_process(script: str, fixed: list, items: list) -> int:
    """``python3 script --one <fixed...> <item>`` for every item in
    turn; the first non-zero exit code, else 0."""
    rc = 0
    for item in items:
        r = subprocess.run([sys.executable, os.path.abspath(script), "--one",
                            *fixed, item], cwd=ROOT)
        rc = rc or r.returncode
    return rc


def record(name: str, line: dict) -> None:
    """Print the line and append it to ``chiprun_out/<name>.jsonl``."""
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", name + ".jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
