"""The benchmark's yardstick: cell lookup, data, work counts, peaks, trace
reduction and the output line. Nothing here imports the program under test
except ``drivers/`` (which drive it) and ``cell.program_path`` (which puts
``src/`` on ``sys.path`` for them)."""
