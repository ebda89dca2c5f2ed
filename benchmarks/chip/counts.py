"""What every model module's counts share: the conventions and the bytes of
each served dtype.

A model module (`models/<name>.py`) counts the operations and bytes its
algorithm needs from a configuration's shapes and the work scheduled
(`serve_loop.BatchCount`), never from the compiler.  A multiply-add counts
as 2 operations.  Bytes are those a kernel has to move at the least, in the
served dtype.
"""

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
