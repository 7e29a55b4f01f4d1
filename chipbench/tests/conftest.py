import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
for p in (str(HERE.parent / "src"), str(HERE), str(HERE / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
