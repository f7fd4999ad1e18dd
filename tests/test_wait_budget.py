"""Every wait a test makes has a limit, and none is over 240 s: a
``subprocess.run``, a ``Popen.communicate``, a ``Thread.join``, an
``Event.wait`` or a ``urlopen`` that never comes back otherwise eats the
whole run's clock (the suite was cut at its 1,470 s twice at PR 49).
The limit of ``tests/conftest.py`` (300 s a test) is the net under these;
they trip first and say what was waited for.  This reads nothing but the
tree."""
import ast
import pathlib
import textwrap

import pytest

TESTS = pathlib.Path(__file__).parent
LIMIT_S = 240
#: ``subprocess`` calls that wait for the child themselves
SUBPROCESS_WAITS = {"run", "call", "check_call", "check_output"}
#: methods that wait; the position of their limit when it is not a keyword
METHOD_WAITS = {"communicate": 1, "join": 0, "wait": 0, "urlopen": 2}
#: keywords that are a wait's limit, whatever takes them (a helper passes
#: ``timeout`` on; the autotuner's is its child process's)
LIMIT_KEYWORDS = {"timeout", "trial_timeout_s"}
#: waits that take no limit: the native aio handle's ``wait()`` returns the
#: count of finished operations and has no such parameter
NO_LIMIT_TO_GIVE = {("test_native_ops.py", "h.wait"),
                    ("test_memory.py", "h.wait")}


def _number(node, scope):
    """The seconds ``node`` says, through a module constant or the default
    of a parameter of the function around it; None when it cannot be read."""
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.Name):
        for names in scope:
            if node.id in names:
                return _number(names[node.id], ())
    return None


def _scopes(tree):
    """(call, [innermost function's defaults, module constants]) pairs."""
    module = {t.id: n.value for n in tree.body if isinstance(n, ast.Assign)
              for t in n.targets if isinstance(t, ast.Name)}

    def walk(node, defaults):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            a = node.args
            named = a.posonlyargs + a.args
            defaults = dict(zip([x.arg for x in named[len(named)
                                                     - len(a.defaults):]],
                                a.defaults))
            defaults.update({x.arg: d for x, d in zip(a.kwonlyargs,
                                                     a.kw_defaults) if d})
        if isinstance(node, ast.Call):
            yield node, (defaults, module)
        for child in ast.iter_child_nodes(node):
            yield from walk(child, defaults)

    yield from walk(tree, {})


def _is_text_join(call):
    """``", ".join(xs)`` / ``os.path.join(a, b)``: one or more arguments
    that are not a number, and no ``timeout``."""
    return bool(call.args) and not call.keywords \
        and not (len(call.args) == 1 and isinstance(call.args[0], ast.Constant)
                 and isinstance(call.args[0].value, (int, float)))


def findings(source, filename="<snippet>"):
    """What is wrong with the waits of ``source``, one line each."""
    out = []
    for call, scope in _scopes(ast.parse(source)):
        f = call.func
        name = f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")
        said = ast.unparse(f)
        where = f"{filename}:{call.lineno} {said}"
        limits = [k.value for k in call.keywords if k.arg in LIMIT_KEYWORDS]
        waits = said.startswith("subprocess.") and name in SUBPROCESS_WAITS
        if name in METHOD_WAITS and not (name == "join"
                                         and _is_text_join(call)):
            waits = True
            if not limits and len(call.args) > METHOD_WAITS[name]:
                limits = [call.args[METHOD_WAITS[name]]]
        if waits and not limits \
                and (filename, said) not in NO_LIMIT_TO_GIVE:
            out.append(f"{where}: waits with no limit")
        for value in limits:
            seconds = _number(value, scope)
            if seconds is None:
                out.append(f"{where}: limit {ast.unparse(value)!r} is not a "
                           f"number this test can read")
            elif seconds > LIMIT_S:
                out.append(f"{where}: limit {seconds} s is over {LIMIT_S} s")
    return out


def test_every_wait_under_tests_has_a_limit_of_at_most_240_s():
    found = [line for path in sorted(TESTS.glob("*.py"))
             for line in findings(path.read_text(), path.name)]
    assert not found, "\n".join(found)


@pytest.mark.parametrize("snippet,said", [
    ("subprocess.run(cmd, capture_output=True)", "no limit"),
    ("subprocess.check_output(cmd)", "no limit"),
    ("subprocess.run(cmd, timeout=600)", "over 240"),
    ("proc.communicate()", "no limit"),
    ("proc.communicate(timeout=360)", "over 240"),
    ("thread.join()", "no limit"),
    ("loop.join(timeout=3600)", "over 240"),
    ("done.wait()", "no limit"),
    ("done.wait(1000)", "over 240"),
    ("urllib.request.urlopen(url)", "no limit"),
    ("urlopen(req, None, 900)", "over 240"),
    ("Autotuner(cfg, f, trial_timeout_s=300)", "over 240"),
    ("subprocess.run(cmd, timeout=budget())", "not a number"),
    ("LONG = 500\nsubprocess.run(cmd, timeout=LONG)", "over 240"),
    ("def post(url, timeout=600):\n    urlopen(url, timeout=timeout)",
     "over 240"),
    ("def post(url, timeout=60):\n    urlopen(url, timeout=timeout)\n"
     "post(u, timeout=400)", "over 240"),
])
def test_a_wait_without_a_limit_or_over_it_is_found(snippet, said):
    found = findings(textwrap.dedent(snippet))
    assert len(found) == 1 and said in found[0], found


@pytest.mark.parametrize("snippet", [
    "subprocess.run(cmd, timeout=240)",
    "proc.communicate(timeout=120)",
    "thread.join(5)",
    "thread.join(timeout=30.0)",
    "done.wait(10)",
    "urllib.request.urlopen(url, timeout=10)",
    "', '.join(names)",
    "os.path.join(root, 'a', 'b')",
    "sep.join(parts)",
    "LIMIT = 200\nsubprocess.run(cmd, timeout=LIMIT)",
    "def post(url, timeout=60):\n    urlopen(url, timeout=timeout)\n"
    "post(u, timeout=120)",
    "subprocess.Popen(cmd)",
])
def test_a_wait_with_a_limit_passes(snippet):
    assert findings(textwrap.dedent(snippet)) == []
