"""Mean time an admission holds the engine, per request admitted inside
the window, read as ``admission_ms.chat`` reads it (engine layer).  None
for a program that keeps no step log."""
import pathlib
import runpy

read = runpy.run_path(str(pathlib.Path(__file__).with_name(
    "admission_ms.chat.py")))["read"]
